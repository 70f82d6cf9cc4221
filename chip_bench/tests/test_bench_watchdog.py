"""The set-up deadline of ``run.py``: a run whose set-up outlasts it ends
non-zero with no result line, naming the stage it reached; once the window
is open the deadline does nothing. Each run is a child process (the
watchdog ends its process) that drives ``run.main`` on the CPU, with the
look for a chip skipped, a tiny cell in the named one's place and the
deadline shortened. Its imports come before its clock starts, so that its
set-up is the cell's alone."""
import json
import os
import subprocess
import sys

from chip_bench import harness

ROOT = harness.ROOT
TINY = {"generator": "powerlaw", "family": "powerlaw", "n_nodes": 300,
        "avg_degree_per_direction": 22.0, "alpha": 1.8, "symmetric": True,
        "graph_seed": 0}
MIX = {"kind": "closed", "clients": 1, "sources_per_query": 64}
DEADLINE_S = 4.0

CHILD = """
import importlib.util, json, sys, time
sys.path[:0] = [{root!r}, {root!r} + "/src"]
import jax
import repro.runtime.service
from chip_bench import harness

spec = importlib.util.spec_from_file_location(
    "bench_run", {root!r} + "/chip_bench/run.py")
run = importlib.util.module_from_spec(spec)
spec.loader.exec_module(run)  # the run's clock starts here
harness.SETUP_DEADLINE_S = {deadline!r}
real_run_cell = harness.run_cell
harness.run_cell = lambda *a, **k: real_run_cell(*a, require_chip=False, **k)
e2e = harness.load_benchmark()["end_to_end"]
harness.load_cell = lambda bench, name, root: harness.Cell(
    name, json.loads({tiny!r}), json.loads({mix!r}), 1, e2e, [])
{stub}
sys.exit(run.main(["--workload", "tiny", "--seed", str(2**31 + 5),
                   "--seconds", "0.5", "--trace", "0"]))
"""


def _run_child(stub: str) -> subprocess.CompletedProcess:
    code = CHILD.format(root=str(ROOT), deadline=DEADLINE_S,
                        tiny=json.dumps(TINY), mix=json.dumps(MIX), stub=stub)
    return subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        timeout=600, cwd=ROOT, env=dict(os.environ, JAX_PLATFORMS="cpu"))


def _result_lines(stdout: str) -> list:
    return [line for line in stdout.splitlines() if line.startswith("{")]


def test_a_set_up_past_the_deadline_ends_with_no_result():
    r = _run_child(
        "harness.LoadGen.step = lambda self: time.sleep(600)")
    assert r.returncode == harness.SETUP_EXPIRED_EXIT, r.stderr[-2000:]
    assert _result_lines(r.stdout) == []
    assert "set-up: graph at " in r.stderr
    assert (f"set-up passed its {DEADLINE_S:g} s deadline; stage reached: "
            "warm-up query 0 (places the operands)") in r.stderr


def test_the_deadline_does_nothing_once_the_window_is_open():
    r = _run_child("""
harness.LoadGen.warm_up = lambda self, reached=None: 0
real_window = harness.LoadGen.closed_window
def late_window(self, clients, seconds):
    time.sleep(max(0.0, run.T_START + harness.SETUP_DEADLINE_S + 1.0
                   - time.perf_counter()))
    return real_window(self, clients, seconds)
harness.LoadGen.closed_window = late_window
""")
    assert r.returncode == 0, r.stderr[-2000:]
    result = json.loads(_result_lines(r.stdout)[-1])
    assert result["correct"] is True and result["attempted"] > 0
    assert result["metrics"]["setup_s"]["value"] < DEADLINE_S
    assert "deadline" not in r.stderr
