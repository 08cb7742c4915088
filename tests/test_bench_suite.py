"""`bench/tests` under tier-1: the tests of the yardstick that decides
every PR (`bench/run.py` under `BENCHMARK.json`) count with the repo's
own.

They cannot be collected next to `tests/`: `bench/tests/conftest.py` is
a second top-level `conftest`, and its modules import `lib`, `run` and
`conftest` by bare name. So the suite runs ONCE a session in a
subprocess of its own, and every test function of `bench/tests` is one
case here, under its own name, read from that run's junit report.

"Once" holds where this whole file lands on one worker: in one process,
and under xdist with `--dist loadfile` (the tier-1 command). Under
`--dist load` every worker that is dealt a case here makes its own run.
"""

import ast
import os
import signal
import subprocess
import sys
import xml.etree.ElementTree as ET

import pytest

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH_TESTS = os.path.join(REPO_ROOT, "bench", "tests")
# bench/tests takes about 75 s alone in one process; a hang fails here
# and does not eat the tier-1 run's clock.
TIME_LIMIT_S = 600


def _bench_test_functions():
    """`module::function` of every test function under bench/tests,
    read from source: importing them needs bench/tests' own conftest."""
    names = []
    for fname in sorted(os.listdir(BENCH_TESTS)):
        if not (fname.startswith("test_") and fname.endswith(".py")):
            continue
        with open(os.path.join(BENCH_TESTS, fname), encoding="utf-8") as f:
            tree = ast.parse(f.read(), filename=fname)
        names.extend(f"{fname[:-3]}::{node.name}" for node in tree.body
                     if isinstance(node, ast.FunctionDef)
                     and node.name.startswith("test_"))
    return names


FUNCTIONS = _bench_test_functions()


@pytest.fixture(scope="module")
def report(tmp_path_factory):
    """One run of bench/tests: its exit code, the tail of its output,
    and per `module::function` the (case, outcome, text) of each case."""
    xml = str(tmp_path_factory.mktemp("bench_suite") / "report.xml")
    proc = subprocess.Popen(
        [sys.executable, "-m", "pytest", "bench/tests", "-q",
         "-p", "no:cacheprovider", "-p", "no:xdist", "-p", "no:randomly",
         f"--junitxml={xml}"],
        cwd=REPO_ROOT, env={**os.environ, "JAX_PLATFORMS": "cpu"},
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        start_new_session=True)
    try:
        out, _ = proc.communicate(timeout=TIME_LIMIT_S)
    except subprocess.TimeoutExpired:
        # bench/tests starts runs of its own: end the whole group.
        os.killpg(proc.pid, signal.SIGKILL)
        out, _ = proc.communicate()
        pytest.fail(f"bench/tests did not end in {TIME_LIMIT_S} s:\n"
                    + out[-4000:])
    cases = {}
    if os.path.exists(xml):
        for case in ET.parse(xml).iter("testcase"):
            module = (case.get("classname") or "").rsplit(".", 1)[-1]
            name = case.get("name") or ""
            fault = next((c for c in case
                          if c.tag in ("failure", "error", "skipped")), None)
            outcome, text = ("passed", "") if fault is None else (
                fault.tag, f"{fault.get('message') or ''}\n{fault.text or ''}")
            cases.setdefault(f"{module}::{name.split('[', 1)[0]}",
                             []).append((name, outcome, text))
    return {"rc": proc.returncode, "tail": out[-4000:], "cases": cases}


@pytest.mark.parametrize("function", FUNCTIONS)
def test_bench(function, report):
    """Every case of one bench/tests function ran and passed (a skip
    counts as a fault: bench/tests skips nothing on the CPU, so a skip
    is an import that went missing)."""
    cases = report["cases"].get(function)
    assert cases, (f"bench/tests reported no case of {function} "
                   f"(exit code {report['rc']}):\n{report['tail']}")
    faults = [f"{name}: {outcome}\n{text[-3000:]}"
              for name, outcome, text in cases if outcome != "passed"]
    assert not faults, ("\n".join(faults)
                        + "\n--- end of bench/tests' output ---\n"
                        + report["tail"])


def test_bench_suite_is_whole(report):
    """The run reported nothing this module does not expose as a case
    (a collection error shows up here by name), and its exit code says
    no more than the cases do: 1 (pytest's "some test failed") only
    where the case of that test's name fails above, else 0."""
    assert sorted(set(report["cases"]) - set(FUNCTIONS)) == [], \
        report["tail"]
    faulty = any(outcome != "passed" for cases in report["cases"].values()
                 for _, outcome, _ in cases)
    assert report["rc"] == (1 if faulty else 0), report["tail"]
