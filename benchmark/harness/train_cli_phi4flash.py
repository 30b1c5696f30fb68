"""Runner `harness.train_cli_phi4flash` (a workload file's `kind` names it
by module path): the train cell of the Phi-4-mini-flash family. Nothing of
the runner is restated: `observe` IS harness/train_cli_xing4.py's — the
window, the rate (`end_to_end`), the in-window checks and every helper
are train_cli's, train_cli_afmoe's and train_cli_xing4's own — rebound
to the three names that say which family it runs: the fixture/correct
child (harness/train_child_phi4flash.py), the fixture's directory (the
one weight fixture of the configuration, `<config>-w<weight_seed>/
phi4flash`, whatever the seed: 7.8 GB) and the run_start keys kept.
`obs["kind"]` stays "train_cli": the readers of harness/readers.py and
span_readers.py ask for that.

`setup_s` leaves out the seconds the child spent on the float32
REFERENCE (`reference_s` of its verdict: computing it, or reading the
copy kept per cell and seed), as train_cli_ling and train_cli_xing4 do.
"""
from __future__ import annotations

import os
import types

from . import train_cli_xing4
from .train_cli import end_to_end, in_window_correct  # noqa: F401

CHILD = "train_child_phi4flash.py"
RUN_START = ("attention_impl_resolved", "attn_bwd_resolved",
             "layers_mamba", "layers_swa", "layers_full", "layers_gmu",
             "layers_cross", "ssm_chunk", "ssm_state", "ssm_inner",
             "mixer_chunk", "lora_impl_resolved")


def fixture_dirs(cell: dict, seed: int) -> dict:
    d = train_cli_xing4.fixture_dirs(cell, seed)
    d["model"] = os.path.join(
        d["root"], f"{cell['config']}-w"
        f"{cell['config_file']['fixture'].get('weight_seed', 0)}",
        "phi4flash")
    return d


observe = types.FunctionType(
    train_cli_xing4.observe.__code__,
    {**vars(train_cli_xing4), "CHILD": CHILD, "RUN_START": RUN_START,
     "fixture_dirs": fixture_dirs},
    "observe", train_cli_xing4.observe.__defaults__)
observe.__doc__ = ("train_cli_xing4.observe with this family's child, "
                   "fixture directory and run_start keys")
