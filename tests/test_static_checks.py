"""graftlint engine tests (DESIGN.md §24): per-rule seeded true-positive
AND clean-negative fixture snippets, suppression-grammar parsing, the
JSON/exit-code CLI contract, the tier-1 gate (the whole package + tools
lint clean), and the compiled-artifact contract checker's tiny CPU run.

Fixture projects are written under tmp as `mobilefinetuner_tpu/<...>`
so the engine's suffix-matched module configuration (STEP_LOOP_MODULES,
THREADED_MODULES, ...) applies to them exactly as to the real tree."""

import json
import os
import subprocess
import sys

import pytest

from mobilefinetuner_tpu.core.static_checks import (
    RULES, Finding, LintError, Project, assert_dots_accumulate_f32,
    collect_emit_sites, hlo_collective_census, hlo_donated_inputs,
    jaxpr_contains, missing_hlo_scopes, parse_suppressions, run_lint)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "tools"))

import graft_lint  # noqa: E402


# ---------------------------------------------------------------------------
# fixture harness
# ---------------------------------------------------------------------------

_CASE = [0]


def lint_snippet(tmp_path, relpath, source, rules=None):
    """Write `source` at an ISOLATED tmp/<caseN>/<relpath> and lint the
    fixture package (isolation: earlier snippets in the same test must
    not leak into later lints)."""
    _CASE[0] += 1
    root = tmp_path / f"case{_CASE[0]}"
    full = root / relpath
    full.parent.mkdir(parents=True, exist_ok=True)
    full.write_text(source)
    return run_lint([str(root / relpath.split("/")[0])], rules=rules)


def names(res):
    return [f.rule for f in res.findings]


# ---------------------------------------------------------------------------
# per-rule true positive + clean negative
# ---------------------------------------------------------------------------

def test_sync_hazard_positive_and_negative(tmp_path):
    res = lint_snippet(tmp_path, "mobilefinetuner_tpu/train/trainer.py", """
def loop(x):
    return float(x)
""", rules=["sync-hazard"])
    assert names(res) == ["sync-hazard"]
    assert res.findings[0].line == 3
    # host-dataflow negative: device_get'd values may be converted
    # freely, and a module OUTSIDE the step-loop set is never flagged
    res = lint_snippet(tmp_path, "mobilefinetuner_tpu/train/trainer.py", """
import jax

def flush(buffered):
    fetched = jax.device_get(buffered)  # graftlint: disable=sync-hazard(the one flush get)
    return [float(m) for m in fetched]
""", rules=["sync-hazard"])
    assert not res.findings and len(res.suppressed) == 1
    res = lint_snippet(tmp_path, "mobilefinetuner_tpu/io/somewhere.py",
                       "def f(x):\n    return float(x)\n",
                       rules=["sync-hazard"])
    assert not res.findings


def test_sync_hazard_self_assignment_is_not_laundered(tmp_path):
    # `x = np.asarray(x)` must still flag: the name being defined by
    # the very statement is not evidence the argument was host data
    res = lint_snippet(tmp_path, "mobilefinetuner_tpu/serve/engine.py", """
import numpy as np

def step(nxt):
    nxt = np.asarray(nxt)
    return nxt
""", rules=["sync-hazard"])
    assert names(res) == ["sync-hazard"]


def test_donation_hazard_positive_and_negative(tmp_path):
    src_bad = """
from mobilefinetuner_tpu.train.trainer import make_train_step

def run(loss_fn, tc, frozen, batch, i):
    step = make_train_step(loss_fn, tc)
    tr, opt = init()
    out = step(tr, frozen, opt, batch, i)
    return tr  # read after donation
"""
    res = lint_snippet(tmp_path, "mobilefinetuner_tpu/cli/common.py",
                       src_bad, rules=["donation-hazard"])
    assert names(res) == ["donation-hazard"]
    src_ok = src_bad.replace("out = step(", "tr, opt, m = step(") \
                    .replace("return tr  # read after donation",
                             "return tr  # rebound by the dispatch")
    res = lint_snippet(tmp_path, "mobilefinetuner_tpu/cli/common.py",
                       src_ok, rules=["donation-hazard"])
    assert not res.findings
    # donate=False builders do not donate
    res = lint_snippet(tmp_path, "mobilefinetuner_tpu/cli/common.py",
                       src_bad.replace("make_train_step(loss_fn, tc)",
                                       "make_train_step(loss_fn, tc, "
                                       "donate=False)"),
                       rules=["donation-hazard"])
    assert not res.findings


def test_donation_hazard_sees_jit_and_lower_compile_chains(tmp_path):
    res = lint_snippet(tmp_path, "mobilefinetuner_tpu/cli/common.py", """
import jax

def run(f, a, b, batch):
    step = jax.jit(f, donate_argnums=(0,))
    compiled = step.lower(a, b, batch).compile()
    out = compiled(a, b, batch)
    return a
""", rules=["donation-hazard"])
    assert names(res) == ["donation-hazard"]


def test_donation_hazard_tracks_self_attribute_steps(tmp_path):
    # the engines' real dispatch pattern: the jitted step lives on
    # self (bound in a builder method, dispatched from another), the
    # donated args are self attributes, and donate_argnums is the
    # conditional `(...) if donate else ()` CPU opt-out spelling
    src_bad = """
import jax

class Engine:
    def build(self, step_py, donate):
        self._step = jax.jit(step_py,
                             donate_argnums=(0, 1) if donate else ())

    def step(self, tok):
        nxt, pk, pv = self._step(
            self.pool_k, self.pool_v, tok)
        return self.pool_k  # read after donation
"""
    res = lint_snippet(tmp_path, "mobilefinetuner_tpu/serve/engine.py",
                       src_bad, rules=["donation-hazard"])
    assert names(res) == ["donation-hazard"]
    assert "self.pool_k" in res.findings[0].message
    # rebinding the attributes from the dispatch output clears them —
    # whether on the dispatch's own statement or a later one
    src_ok = src_bad.replace(
        "nxt, pk, pv = self._step(",
        "nxt, self.pool_k, self.pool_v = self._step(").replace(
        "return self.pool_k  # read after donation",
        "return self.pool_k  # rebound by the dispatch")
    res = lint_snippet(tmp_path, "mobilefinetuner_tpu/serve/engine.py",
                       src_ok, rules=["donation-hazard"])
    assert not res.findings
    src_ok2 = src_bad.replace(
        "return self.pool_k  # read after donation",
        "self.pool_k, self.pool_v = pk, pv\n        return self.pool_k")
    res = lint_snippet(tmp_path, "mobilefinetuner_tpu/serve/engine.py",
                       src_ok2, rules=["donation-hazard"])
    assert not res.findings


def test_untraced_branch_positive_and_negative(tmp_path):
    res = lint_snippet(tmp_path, "mobilefinetuner_tpu/ops/foo.py", """
import jax

@jax.jit
def f(x):
    if x > 0:
        return x
    return -x
""", rules=["untraced-branch"])
    assert names(res) == ["untraced-branch"]
    # negatives: is-None / dict-membership / static attrs / static args
    res = lint_snippet(tmp_path, "mobilefinetuner_tpu/ops/foo.py", """
import jax
from functools import partial

@partial(jax.jit, static_argnames=("mode",))
def f(x, y, mode="a"):
    if x is None:
        return y
    if "k" in y:
        return x
    if x.shape[0] > 2:
        return x
    if mode == "b":
        return x
    return x + 1
""", rules=["untraced-branch"])
    assert not res.findings


def test_dtype_accum_positive_and_negative(tmp_path):
    res = lint_snippet(tmp_path, "mobilefinetuner_tpu/ops/foo.py", """
import jax.numpy as jnp

def f(a, b):
    return jnp.einsum("ij,jk->ik", a, b)
""", rules=["dtype-accum"])
    assert names(res) == ["dtype-accum"]
    res = lint_snippet(tmp_path, "mobilefinetuner_tpu/ops/foo.py", """
import jax.numpy as jnp

def f(a, b):
    return jnp.einsum("ij,jk->ik", a, b,
                      preferred_element_type=jnp.float32)
""", rules=["dtype-accum"])
    assert not res.findings
    # outside models//ops/ the rule does not apply (host-side math)
    res = lint_snippet(tmp_path, "mobilefinetuner_tpu/cli/common.py",
                       "import jax.numpy as jnp\n"
                       "def f(a, b):\n"
                       "    return jnp.matmul(a, b)\n",
                       rules=["dtype-accum"])
    assert not res.findings


def test_emit_schema_positive_and_negative(tmp_path):
    res = lint_snippet(tmp_path, "mobilefinetuner_tpu/core/foo.py", """
def f(tel):
    tel.emit("bogus_event", step=1)
""", rules=["emit-schema"])
    assert names(res) == ["emit-schema"]
    assert "bogus_event" in res.findings[0].message
    res = lint_snippet(tmp_path, "mobilefinetuner_tpu/core/foo.py", """
def f(tel, sink):
    tel.emit("run_start", config={})
    sink(event="step_stats", step=1)
""", rules=["emit-schema"])
    assert not res.findings


def test_emit_fields_positive_and_negative(tmp_path):
    # a literal-kwarg emit site that silently drops a required schema
    # field is the dead-taxonomy bug in miniature: the round-23 `run`
    # record contract (DESIGN.md §28) only holds if every field is
    # carried explicitly (None included)
    res = lint_snippet(tmp_path, "mobilefinetuner_tpu/core/foo.py", """
def f(tel):
    tel.emit("trend", metric="tok_s", config="c", platform="tpu",
             value=1.0, median=1.0, mad=0.0, z=0.0,
             direction="higher", regressed=False, run="r01")
""", rules=["emit-fields"])
    assert names(res) == ["emit-fields"]
    assert "n" in res.findings[0].message.split("field(s)")[1]
    # full field set: clean
    res = lint_snippet(tmp_path, "mobilefinetuner_tpu/core/foo.py", """
def f(tel):
    tel.emit("trend", metric="tok_s", config="c", platform="tpu",
             value=1.0, median=None, mad=None, z=None,
             direction=None, regressed=False, run="r01", n=1)
""", rules=["emit-fields"])
    assert not res.findings


def test_emit_fields_skips_splats_and_unknown_events(tmp_path):
    res = lint_snippet(tmp_path, "mobilefinetuner_tpu/core/foo.py", """
def f(tel, payload):
    tel.emit("run", **payload)       # runtime validate_event's job
    tel.emit("bogus_event", step=1)  # emit-schema's job, not ours
    tel.emit(name, step=1)           # dynamic event name: unknowable
""", rules=["emit-fields"])
    assert not res.findings


def test_serve_taxonomy_positive_and_negative(tmp_path):
    from mobilefinetuner_tpu.core.telemetry import (REQUEST_PHASES,
                                                    REQUEST_REASONS)
    lines = ["def f(emit):"]
    for p in REQUEST_PHASES:
        lines.append(f'    emit(phase="{p}")')
    for r in sorted(REQUEST_REASONS):
        lines.append(f'    emit(reason="{r}")')
    clean = "\n".join(lines) + "\n"
    res = lint_snippet(tmp_path, "mobilefinetuner_tpu/serve/engine.py",
                       clean, rules=["serve-taxonomy"])
    assert not res.findings
    res = lint_snippet(tmp_path, "mobilefinetuner_tpu/serve/engine.py",
                       clean + '\ndef g(emit):\n'
                               '    emit(phase="warp_speed")\n',
                       rules=["serve-taxonomy"])
    assert names(res) == ["serve-taxonomy"]
    assert "warp_speed" in res.findings[0].message


def test_lock_discipline_positive_and_negative(tmp_path):
    base = """
import threading

GRAFT_SHARED_STATE = {{
    "Box": {{"lock": "_lock", "guarded": ["_val"],
             "locked_helpers": ["_bump"], "channels": []}},
}}


class Box:
    def __init__(self):
        self._lock = threading.Lock()
        self._val = 0

    def _bump(self):
        self._val += 1

    def set(self, v):
        {set_body}

    def get(self):
        {get_body}
"""
    ok = base.format(
        set_body="with self._lock:\n            self._val = v",
        get_body="with self._lock:\n            return self._val")
    res = lint_snippet(tmp_path, "mobilefinetuner_tpu/io/async_ckpt.py",
                       ok, rules=["lock-discipline"])
    assert not res.findings
    # guarded access outside the lock + locked helper called unlocked
    bad = base.format(set_body="self._val = v",
                      get_body="self._bump()\n        return 0")
    res = lint_snippet(tmp_path, "mobilefinetuner_tpu/io/async_ckpt.py",
                       bad, rules=["lock-discipline"])
    assert sorted(names(res)) == ["lock-discipline", "lock-discipline"]
    msgs = " ".join(f.message for f in res.findings)
    assert "_val" in msgs and "_bump" in msgs
    # a threaded module with NO declaration is itself a finding
    res = lint_snippet(tmp_path, "mobilefinetuner_tpu/data/prefetch.py",
                       "x = 1\n", rules=["lock-discipline"])
    assert names(res) == ["lock-discipline"]
    assert "GRAFT_SHARED_STATE" in res.findings[0].message


def test_no_jax_import_positive_and_negative(tmp_path):
    # policy "never": even a lazy in-function import fails metrics_http
    res = lint_snippet(tmp_path,
                       "mobilefinetuner_tpu/core/metrics_http.py",
                       "def f():\n    import jax\n    return jax\n",
                       rules=["no-jax-import"])
    assert names(res) == ["no-jax-import"]
    # policy "toplevel": trace.py may import jax lazily, not at module
    # level
    res = lint_snippet(tmp_path, "mobilefinetuner_tpu/core/trace.py",
                       "def f():\n    import jax\n    return jax\n",
                       rules=["no-jax-import"])
    assert not res.findings
    res = lint_snippet(tmp_path, "mobilefinetuner_tpu/core/trace.py",
                       "from jax import profiler\n",
                       rules=["no-jax-import"])
    assert names(res) == ["no-jax-import"]
    # "toplevel" means import-time execution, not lexical depth: the
    # `try: import jax` idiom still runs at module level
    res = lint_snippet(tmp_path, "mobilefinetuner_tpu/core/trace.py", """
try:
    import jax
except ImportError:
    jax = None
""", rules=["no-jax-import"])
    assert names(res) == ["no-jax-import"]


# ---------------------------------------------------------------------------
# suppression grammar
# ---------------------------------------------------------------------------

def test_suppression_same_line_standalone_and_comma_reasons():
    src = ("x = float(y)  # graftlint: disable=sync-hazard(why, with a comma)\n"
           "# graftlint: disable=dtype-accum(covers the NEXT line)\n"
           "z = 1\n")
    table, bad = parse_suppressions(src, "f.py")
    assert not bad
    assert table[1] == {"sync-hazard": "why, with a comma"}
    assert table[3] == {"dtype-accum": "covers the NEXT line"}


def test_suppression_requires_reason_and_known_rule():
    table, bad = parse_suppressions(
        "x = 1  # graftlint: disable=sync-hazard\n", "f.py")
    assert not table.get(1) and len(bad) == 1
    assert bad[0].rule == "bad-suppression"
    table, bad = parse_suppressions(
        "x = 1  # graftlint: disable=not-a-rule(reason)\n", "f.py")
    assert not table.get(1) and len(bad) == 1
    assert "unknown rule" in bad[0].message


def test_reasonless_suppression_is_a_finding_not_an_exemption(tmp_path):
    res = lint_snippet(tmp_path, "mobilefinetuner_tpu/train/trainer.py", """
def loop(x):
    return float(x)  # graftlint: disable=sync-hazard
""", rules=["sync-hazard"])
    assert sorted(names(res)) == ["bad-suppression", "sync-hazard"]


# ---------------------------------------------------------------------------
# CLI contract: JSON shape + bench_compare-style exit codes
# ---------------------------------------------------------------------------

def test_graft_lint_json_output_and_exit_codes(tmp_path, capsys):
    pkg = tmp_path / "mobilefinetuner_tpu" / "ops"
    pkg.mkdir(parents=True)
    (pkg / "foo.py").write_text(
        "import jax.numpy as jnp\n"
        "def f(a, b):\n"
        "    return jnp.matmul(a, b)\n")
    rc = graft_lint.main([str(tmp_path / "mobilefinetuner_tpu"),
                          "--format", "json"])
    out = json.loads(capsys.readouterr().out)
    assert rc == 2
    assert out["counts"] == {"findings": 1, "suppressed": 0}
    f = out["findings"][0]
    assert {"rule", "path", "line", "col", "message", "suppressed",
            "reason"} <= set(f)
    assert f["rule"] == "dtype-accum"
    assert f["path"].endswith("ops/foo.py")
    # clean tree -> 0
    (pkg / "foo.py").write_text("x = 1\n")
    assert graft_lint.main([str(tmp_path / "mobilefinetuner_tpu"),
                            "--format", "json"]) == 0
    capsys.readouterr()
    # engine errors -> 1 (bad path, unknown rule, syntax error)
    assert graft_lint.main([str(tmp_path / "nope")]) == 1
    assert graft_lint.main([str(tmp_path / "mobilefinetuner_tpu"),
                            "--rules", "made-up"]) == 1
    (pkg / "foo.py").write_text("def broken(:\n")
    assert graft_lint.main([str(tmp_path / "mobilefinetuner_tpu")]) == 1
    capsys.readouterr()


def test_graft_lint_cli_subprocess_smoke():
    """The real entry point, end to end: `--list-rules` exits 0 and
    names every shipped rule (the CLI imports only the stdlib half, so
    this stays fast)."""
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "graft_lint.py"),
         "--list-rules"], capture_output=True, text=True, timeout=60)
    assert out.returncode == 0
    for rule in RULES:
        assert rule in out.stdout


# ---------------------------------------------------------------------------
# the tier-1 gate: the whole repo lints clean
# ---------------------------------------------------------------------------

def test_package_and_tools_lint_clean():
    """THE enforcement test (the CI satellite): zero unsuppressed
    findings over mobilefinetuner_tpu/ + tools/ with every shipped
    rule. A new module that breaks an invariant — or suppresses one
    without a reason — fails tier-1 here."""
    res = run_lint([os.path.join(REPO, "mobilefinetuner_tpu"),
                    os.path.join(REPO, "tools")])
    assert not res.findings, "\n" + "\n".join(
        f.render() for f in res.findings)
    # the suppression inventory is intentional, reasoned, and small —
    # every entry names its rule and carries prose
    assert all(f.reason for f in res.suppressed)
    assert len(res.suppressed) < 40, "suppressions are creeping: " \
        "fix findings instead of papering over them"


def test_threaded_modules_all_declare_shared_state():
    """Every threaded host subsystem carries a GRAFT_SHARED_STATE
    declaration (the lock-discipline rule's input, and the reader's
    map of the module's cross-thread contract)."""
    from mobilefinetuner_tpu.core.static_checks import THREADED_MODULES
    # the r22 serve router lives in tools/, so the scan covers both
    # roots (run_lint's tier-1 gate above already does)
    proj = Project([os.path.join(REPO, "mobilefinetuner_tpu"),
                    os.path.join(REPO, "tools")])
    declared = {m.relpath for m in proj.modules
                if "GRAFT_SHARED_STATE" in m.source}
    for suffix in THREADED_MODULES:
        assert any(p.endswith(suffix) for p in declared), suffix


# ---------------------------------------------------------------------------
# compiled-artifact helpers (unit level, synthetic HLO)
# ---------------------------------------------------------------------------

_HLO = '''HloModule jit_step, input_output_alias={ {0}: (0, {}, may-alias), {1}: (2, {}, may-alias) }, entry_computation_layout={()->f32[]}

ENTRY main {
  %ag = f32[8]{0} all-gather(f32[2]{0} %p0), replica_groups={}, metadata={op_name="jit(step)/jit(main)/transpose(jvp(embed))/gather"}
  %ar.1 = f32[8]{0} all-reduce-start(f32[8]{0} %ag), metadata={op_name="jit(step)/mlp/add"}
  %ar.2 = f32[8]{0} all-reduce-done(f32[8]{0} %ar.1)
  %r = f32[] dot(f32[8]{0} %ar.2, f32[8]{0} %ag), metadata={op_name="jit(step)/loss/dot"}
}
'''


def test_hlo_census_donation_and_scope_helpers():
    census = hlo_collective_census(_HLO)
    assert census["all-gather"] == 1
    assert census["all-reduce"] == 1  # -start counted once, -done not
    assert census["reduce-scatter"] == 0
    assert hlo_donated_inputs(_HLO) == 2
    assert missing_hlo_scopes(_HLO, ["embed", "mlp", "loss"]) == []
    # "emb" must NOT match inside "embed" (component-delimited match)
    assert missing_hlo_scopes(_HLO, ["emb", "optimizer"]) == \
        ["emb", "optimizer"]


_HLO_TPU_RS = '''HloModule jit_step

%all-reduce-scatter.4.clone (input_0: f32[256,768]) -> f32[256,192] {
  %input_0 = f32[256,768]{1,0} parameter(0)
  %all-reduce.39 = f32[256,768]{1,0} all-reduce(f32[256,768]{1,0} %input_0), replica_groups={{0,1,2,3}}
  ROOT %ds = f32[256,192]{1,0} dynamic-slice(%all-reduce.39, %z, %o)
}

ENTRY main {
  %ag = bf16[256,768]{1,0} all-gather(bf16[256,192]{1,0} %p0), metadata={op_name="jit(step)/while/body/attention/convert_element_type"}
  %fusion.367 = f32[256,192]{1,0} fusion(f32[256,768]{1,0} %d), kind=kCustom, calls=%all-reduce-scatter.4.clone, metadata={op_name="jit(step)/transpose(jvp())/while/body/attention/dot_general"}
  %a2a = bf16[4,8]{1,0} all-to-all(bf16[4,8]{1,0} %e), metadata={op_name="jit(step)/jvp(embed)/gather"}
}
'''


def test_census_counts_the_tpu_reduce_scatter_fusion_and_filters_scopes():
    """The TPU's reduce-scatter is a fusion calling an
    `all-reduce-scatter` computation: one reduce-scatter, and the
    all-reduce inside it is not counted; `scopes` keeps the collectives
    whose op_name has one of them as a path component."""
    census = hlo_collective_census(_HLO_TPU_RS)
    assert census["reduce-scatter"] == 1 and census["all-reduce"] == 0
    assert census["all-gather"] == 1 and census["all-to-all"] == 1
    block = hlo_collective_census(_HLO_TPU_RS, scopes=("attention", "mlp"))
    assert (block["all-gather"], block["reduce-scatter"],
            block["all-to-all"]) == (1, 1, 0)
    assert hlo_collective_census(_HLO, scopes=("mlp",))["all-reduce"] == 1


def test_jaxpr_helpers_find_dots_and_pallas():
    import jax.numpy as jnp

    def good(a, b):
        return jnp.einsum("ij,jk->ik", a, b,
                          preferred_element_type=jnp.float32)

    def bad(a, b):
        return a @ b  # follows input dtype

    a = jnp.zeros((4, 4), jnp.bfloat16)
    assert_dots_accumulate_f32(good, a, a)
    with pytest.raises(AssertionError):
        assert_dots_accumulate_f32(bad, a, a)
    assert not jaxpr_contains(good, "pallas_call", a, a)
    assert jaxpr_contains(good, "dot_general", a, a)


def test_collect_emit_sites_sees_both_spellings(tmp_path):
    full = tmp_path / "mobilefinetuner_tpu" / "m.py"
    full.parent.mkdir(parents=True)
    full.write_text("tel.emit('run_start', config={})\n"
                    "sink(event='checkpoint', step=1)\n")
    found = collect_emit_sites(
        Project([str(tmp_path / "mobilefinetuner_tpu")]).modules)
    assert set(found) == {"run_start", "checkpoint"}


def test_finding_render_and_lint_error():
    f = Finding("sync-hazard", "a/b.py", 3, 7, "boom",
                suppressed=True, reason="why")
    assert f.render() == "a/b.py:3:7: sync-hazard: boom  [suppressed: why]"
    with pytest.raises(LintError):
        run_lint([os.path.join(REPO, "mobilefinetuner_tpu")],
                 rules=["no-such-rule"])


# ---------------------------------------------------------------------------
# compiled-artifact contract checker: tiny CPU run + violation exit
# ---------------------------------------------------------------------------

def test_check_compiled_contracts_cpu(tmp_path, capsys):
    """The pinned contracts hold on this container (retraces, donation,
    collective census, scopes for train/decode/multitenant programs),
    and a tampered pin exits 2 naming the drifted key."""
    import check_compiled_contracts as ccc
    assert ccc.main(["--programs",
                     "train_gpt2_lora,decode_gpt2_paged,"
                     "multitenant_gpt2"]) == 0
    capsys.readouterr()
    with open(os.path.join(REPO, "tools", "compiled_contracts.json")) as f:
        doc = json.load(f)
    for prog in ("train_gpt2_lora", "train_gpt2_fsdp",
                 "decode_gpt2_paged", "multitenant_gpt2"):
        c = doc["programs"][prog]
        assert set(c) == {"retraces", "donated", "collectives", "scopes"}
    # one executable across 3 same-shape calls, pinned
    assert doc["programs"]["train_gpt2_lora"]["retraces"] == 1
    assert doc["programs"]["train_gpt2_lora"]["donated"] > 0
    assert doc["programs"]["decode_gpt2_paged"]["donated"] == 2  # pools
    # tamper: a surprise all-gather in the solo train program must fail
    doc["programs"]["train_gpt2_lora"]["collectives"]["all-gather"] = 3
    tampered = tmp_path / "contracts.json"
    tampered.write_text(json.dumps(doc))
    rc = ccc.main(["--contracts", str(tampered),
                   "--programs", "train_gpt2_lora"])
    out = capsys.readouterr().out
    assert rc == 2
    assert "VIOLATION" in out and "collectives" in out
