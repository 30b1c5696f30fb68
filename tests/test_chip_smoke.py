"""chip_smoke.py's contract, as far as a machine without a chip can show
it: no chip means a non-zero exit and never the chip line; the compile
cache is placed from outside or at one fixed path; a parent that starts
chip-needing children has not touched a JAX backend itself.

The cheap cases ride tier-1. The full tiny-size CPU rehearsals of every
phase (one chip, and the four-chip option on four virtual devices) and the
serve_bench exit-code case are `slow`: the builder runs them before a chip
call (`pytest tests/test_chip_smoke.py -m slow`)."""

from __future__ import annotations

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(ROOT, "chip_smoke.py")
# the smoke's own environment: a CPU, no cache placed from outside, and
# none of the suite's eight virtual devices (conftest exports XLA_FLAGS)
CPU_ENV = {**os.environ, "JAX_PLATFORMS": "cpu"}
CPU_ENV.pop("JAX_COMPILATION_CACHE_DIR", None)
CPU_ENV.pop("XLA_FLAGS", None)


def run_smoke(tmp_path, *flags, timeout=600):
    p = subprocess.run(
        [sys.executable, SMOKE, "--out", str(tmp_path / "out"),
         "--work_dir", str(tmp_path / "work"), *flags],
        capture_output=True, text=True, env=CPU_ENV, timeout=timeout)
    lines = [json.loads(ln) for ln in p.stdout.splitlines()
             if ln.startswith("{")]
    return p, lines


def test_no_chip_means_nonzero_exit_and_never_the_chip_line(tmp_path):
    """The default invocation where JAX finds only a CPU: the device
    phase fails, nothing else runs, the last line says ok false."""
    p, lines = run_smoke(tmp_path)
    assert p.returncode != 0
    assert lines[-1]["ok"] is False and lines[-1]["failed"] == "device"
    assert lines[-1]["device"] is None
    assert not any(ln.get("ok") is True and "phase" not in ln
                   for ln in lines)
    assert '"ok": true' not in p.stdout
    dev = next(ln for ln in lines if ln.get("phase") == "device")
    assert dev["device"]["platform"] == "cpu" and dev["ok"] is False
    assert not any(ln.get("phase") == "fixtures" for ln in lines)


def test_compile_cache_dir_is_fixed_inside_the_checkout(tmp_path):
    """Unset, the cache directory is ONE path inside the checkout — the
    same from any working directory and any process (no temporary name,
    pid or time in it); set from outside, that is the directory."""
    code = ("import sys; sys.path.insert(0, sys.argv[1]);"
            "from mobilefinetuner_tpu.core.compile_cache import cache_dir;"
            "print(cache_dir())")
    seen = []
    for cwd in (tmp_path, ROOT):
        out = subprocess.run([sys.executable, "-c", code, ROOT], cwd=cwd,
                             env=CPU_ENV, capture_output=True, text=True,
                             check=True).stdout.strip()
        seen.append(out)
    assert seen[0] == seen[1] == os.path.join(ROOT, ".jax_cache")
    placed = subprocess.run(
        [sys.executable, "-c", code, ROOT], cwd=tmp_path,
        env={**CPU_ENV, "JAX_COMPILATION_CACHE_DIR": "/some/dir"},
        capture_output=True, text=True, check=True).stdout.strip()
    assert placed == "/some/dir"


def test_enable_compile_cache_leaves_a_placed_cache_alone(monkeypatch):
    """With JAX_COMPILATION_CACHE_DIR set the helper sets no directory
    in code (JAX reads the variable itself); unset, it sets the fixed
    in-checkout one."""
    import jax
    from mobilefinetuner_tpu.core import compile_cache as cc
    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv(cc.ENV_VAR, "/some/dir")
        assert cc.enable_compile_cache() == "/some/dir"
        assert jax.config.jax_compilation_cache_dir == before
        monkeypatch.delenv(cc.ENV_VAR)
        assert cc.enable_compile_cache() == cc.DEFAULT_DIR
        assert jax.config.jax_compilation_cache_dir == cc.DEFAULT_DIR
        assert set(cc.cache_counts()) == {"requests", "hits", "writes"}
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_smoke_parent_spawns_without_importing_jax(tmp_path):
    """One process per chip: the parent of chip_smoke.py has not even
    imported jax when it starts a child (the stub child 'fails', so the
    parent stops after the device phase)."""
    code = f"""
import json, sys
sys.path.insert(0, {ROOT!r})
import chip_smoke
seen = []
class Done:
    returncode = 2
def fake_run(cmd, **kw):
    seen.append("jax" in sys.modules)
    return Done()
chip_smoke.subprocess.run = fake_run
rc = chip_smoke.main(["--out", {str(tmp_path / 'o')!r},
                      "--work_dir", {str(tmp_path / 'w')!r}])
print(json.dumps({{"rc": rc, "jax_at_spawn": seen,
                   "jax_at_exit": "jax" in sys.modules}}))
"""
    out = subprocess.run([sys.executable, "-c", code], env=CPU_ENV,
                         capture_output=True, text=True, check=True).stdout
    res = json.loads(out.splitlines()[-1])
    assert res == {"rc": 1, "jax_at_spawn": [False], "jax_at_exit": False}


def test_router_parent_has_no_backend_when_it_spawns(tmp_path):
    """tools/serve_bench.py --router: the fleet is started before this
    process initialises a backend (the replicas need the chips), and a
    parent that already holds one refuses to start a fleet at all."""
    code = f"""
import json, sys
sys.path.insert(0, {os.path.join(ROOT, 'tools')!r})
import serve_bench
from mobilefinetuner_tpu.parallel.host_devices import backend_initialized
seen = []
class Spawned(Exception):
    pass
def fake_popen(cmd, **kw):
    seen.append(backend_initialized())
    raise Spawned()
import subprocess
subprocess.Popen = fake_popen
argv = ["--router", "2", "--router_baseline", "1", "--model", "tiny-gpt2",
        "--telemetry_out", {str(tmp_path / 'fleet.jsonl')!r}]
try:
    serve_bench.main(argv)
except Spawned:
    pass
import jax
jax.devices()
try:
    serve_bench.main(argv)
    refused = None
except SystemExit as e:
    refused = str(e)
print(json.dumps({{"at_spawn": seen, "refused": refused}}))
"""
    out = subprocess.run([sys.executable, "-c", code], env=CPU_ENV,
                         capture_output=True, text=True, check=True).stdout
    res = json.loads(out.splitlines()[-1])
    assert res["at_spawn"] == [False]          # spawned once, backend-free
    assert "initialised a JAX backend" in res["refused"]


def test_rows_with_errors_names_the_failed_configs():
    sys.path.insert(0, os.path.join(ROOT, "tools"))
    import serve_bench
    rows = [{"config": "a", "terminal": {"finished": 8, "error": 0}},
            {"config": "b", "terminal": {"finished": 0, "error": 8}},
            {"config": "b_replica1"}]
    assert serve_bench.rows_with_errors(rows) == ["b"]


# --------------------------- slow: the rehearsals ---------------------------

def assert_rehearsal(p, lines, phases):
    assert p.returncode == 0, p.stdout[-3000:] + p.stderr[-2000:]
    assert all(ln.get("rehearsal") is True for ln in lines)
    # never the chip line (nor its failed form): no bare {"ok": ...}
    assert not any(ln.startswith('{"ok"') for ln in p.stdout.splitlines())
    assert not any("phase" not in ln and "ok" in ln for ln in lines)
    assert lines[-1]["rehearsal_passed"] is True
    got = [ln["phase"] for ln in lines if "phase" in ln]
    assert got == phases, got
    assert all(ln.get("ok", True) for ln in lines if "phase" in ln)


@pytest.mark.slow
def test_rehearsal_runs_every_one_chip_phase(tmp_path):
    p, lines = run_smoke(tmp_path, "--rehearse")
    assert_rehearsal(p, lines, [
        "start", "device", "fixtures", "train_B8_S32", "train_B2_S64",
        "train_resume", "eval", "numerics", "serve"])
    resume = next(ln for ln in lines if ln.get("phase") == "train_resume")
    first = next(ln for ln in lines if ln.get("phase") == "train_B8_S32")
    assert resume["losses"] == first["losses"][resume["resumed_from_step"]:]


@pytest.mark.slow
def test_four_chip_option_runs_only_its_own_phases(tmp_path):
    p, lines = run_smoke(tmp_path, "--rehearse", "--chips", "4")
    assert_rehearsal(p, lines, [
        "start", "device", "fixtures", "train_fsdp4_S32", "train_fsdp1_S32",
        "fsdp4_vs_1_S32", "train_fsdp4_S64", "train_fsdp1_S64",
        "fsdp4_vs_1_S64", "tp_decode"])
    assert lines[-1]["device"]["count"] == 4
    for S in (32, 64):
        fsdp = next(ln for ln in lines
                    if ln.get("phase") == f"fsdp4_vs_1_S{S}")
        assert fsdp["collectives_fsdp4"]["all-gather"] > 0
        assert set(fsdp["attention_impl_resolved"]) == \
            set(fsdp["tpu_custom_calls"]) == {"fsdp4", "fsdp1"}


@pytest.mark.slow
def test_four_chip_option_fails_with_fewer_devices(tmp_path):
    """Never fall back to one: --chips 4 where JAX has a single device
    fails in the device phase (the rehearsal's virtual devices are what
    the parent sets up, so take them away again)."""
    env = CPU_ENV
    code = (f"import sys; sys.argv = ['chip_smoke.py', '--phase', 'device',"
            f" '--chips', '4', '--rehearse', '--out', {str(tmp_path)!r},"
            f" '--work_dir', {str(tmp_path)!r}];"
            f" sys.path.insert(0, {ROOT!r}); import chip_smoke;"
            f" sys.exit(chip_smoke.main())")
    p = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode == 2
    dev = json.loads(p.stdout.splitlines()[-1])
    assert dev["ok"] is False and dev["device"]["count"] == 1


@pytest.mark.slow
def test_serve_bench_fails_when_a_clean_run_ends_in_error(monkeypatch,
                                                          tmp_path):
    """A step that cannot run is CONTAINED by on_step_error=fail_active
    (every active request ends in state error) — with no fault injected
    that must not exit 0: on the chip it was a kernel the compiler
    refused, reported as a successful run with zero goodput."""
    sys.path.insert(0, os.path.join(ROOT, "tools"))
    import serve_bench

    def break_every_step(engine, spec, hang_s=2.0):
        def hook(step):
            raise RuntimeError("this step cannot run")
        engine.step_hook = hook
        return None       # nothing "armed": not the --inject harness

    monkeypatch.setattr(serve_bench, "install_inject", break_every_step)
    argv = ["--model", "tiny-gpt2", "--requests", "4", "--rate", "50",
            "--max_prompt", "16", "--max_new", "4", "--num_slots", "2",
            "--num_blocks", "32", "--drain", "0"]
    with pytest.raises(SystemExit) as ei:
        serve_bench.main(argv)
    assert "state `error`" in str(ei.value)
    # the same run under the fault harness is the harness's business
    monkeypatch.setattr(serve_bench, "install_inject",
                        lambda e, s, hang_s=2.0: break_every_step(e, s)
                        or [1])
    assert serve_bench.main(argv + ["--inject", "step_error:1"]) == 0
