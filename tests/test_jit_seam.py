"""The one jit seam: `scripts/check_metrics_coverage.check_jit_entry_points`
finds `jax.jit` in every spelling outside `telemetry/compilation.py`, an
`instrumented_jit` without a scope of `DEVICE_SCOPES`, and a
`DEVICE_SCOPES` that differs from docs/telemetry.md's device-scope table
(each rule against a planted bad file); the seam itself refuses a scope
the table lacks."""

import os
import sys

import pytest

from hyperspace_tpu import telemetry

from span_seam_helpers import REPO_ROOT

sys.path.insert(0, os.path.join(REPO_ROOT, "scripts"))
try:
    from check_metrics_coverage import (check_jit_entry_points,
                                        device_scope_table)
finally:
    sys.path.pop(0)

GOOD = (
    "from functools import partial\n"
    "from hyperspace_tpu import telemetry\n"
    "from hyperspace_tpu.telemetry import instrumented_jit\n"
    "# mentions jax.jit in prose only: `@jax.jit`\n"
    "\n"
    "\n"
    "@instrumented_jit('a', scope='hs.gather')\n"
    "def a(x):\n"
    "    return x\n"
    "\n"
    "\n"
    "b = partial(telemetry.instrumented_jit, 'b', scope='hs.sort',\n"
    "            static_argnames=('k',))(lambda x, k: x)\n"
    "c = instrumented_jit('c', lambda x: x, scope='hs.stage')\n")


def _lint(tmp_path, source, doc=None):
    pkg = tmp_path / "pkg"
    (pkg / "telemetry").mkdir(parents=True)
    (pkg / "ok.py").write_text(GOOD)
    # the seam's own module may call jax.jit
    (pkg / "telemetry" / "compilation.py").write_text(
        "import jax\nf = jax.jit(lambda x: x)\n")
    if source is not None:
        (pkg / "bad.py").write_text(source)
    doc_path = None
    if doc is not None:
        doc_path = str(tmp_path / "telemetry.md")
        with open(doc_path, "w") as f:
            f.write(doc)
    return check_jit_entry_points(str(pkg), doc_path)


def test_a_good_package_passes(tmp_path):
    assert _lint(tmp_path, None) == []


@pytest.mark.parametrize("source", [
    "import jax\n\n\ndef f(x):\n    return jax.jit(lambda y: y)(x)\n",
    "import jax\n\n\n@jax.jit\ndef f(x):\n    return x\n",
    "@__import__('jax').jit\ndef f(x):\n    return x\n",
    "from functools import partial\nimport jax\n\n\n"
    "@partial(jax.jit, static_argnames=('n',))\ndef f(x, n):\n    return x\n",
    "@__import__('functools').partial(__import__('jax').jit,\n"
    "                                 static_argnames=('n',))\n"
    "def f(x, n):\n    return x\n",
    "from jax import jit\n\nf = jit(lambda x: x)\n",
], ids=["call", "decorator", "dunder_import", "partial", "dunder_partial",
        "from_import"])
def test_every_spelling_of_a_raw_jit_fails(tmp_path, source):
    failures = _lint(tmp_path, source)
    assert len(failures) == 1 and "bad.py" in failures[0], failures
    assert "instrumented_jit" in failures[0]


@pytest.mark.parametrize("source, said", [
    ("from hyperspace_tpu.telemetry import instrumented_jit\n\n"
     "f = instrumented_jit('f', lambda x: x)\n", "without a scope"),
    ("from functools import partial\n"
     "from hyperspace_tpu import telemetry\n\n"
     "f = partial(telemetry.instrumented_jit, 'f')(lambda x: x)\n",
     "without a scope"),
    ("from hyperspace_tpu.telemetry import instrumented_jit\n\n"
     "f = instrumented_jit('f', lambda x: x, scope='hs.nowhere')\n",
     "'hs.nowhere' is not a key"),
    ("from hyperspace_tpu.telemetry import instrumented_jit\n\n"
     "S = 'hs.stage'\nf = instrumented_jit('f', lambda x: x, scope=S)\n",
     "a computed value"),
], ids=["missing", "missing_in_partial", "unknown", "computed"])
def test_an_instrumented_jit_without_a_known_scope_fails(tmp_path, source,
                                                         said):
    failures = _lint(tmp_path, source)
    assert len(failures) == 1 and "bad.py:" in failures[0], failures
    assert said in failures[0]


def _table(names):
    rows = "".join(f"| `{n}` | somewhere | something |\n" for n in names)
    return ("# Telemetry\n\n| Device scope | Where | What |\n"
            "| --- | --- | --- |\n" + rows + "\nAfter the table.\n")


@pytest.mark.parametrize("case", ["matches", "row_missing", "row_extra",
                                  "no_table"])
def test_the_device_scope_table_matches_device_scopes(tmp_path, case):
    names = sorted(telemetry.DEVICE_SCOPES)
    doc = {"matches": _table(names),
           "row_missing": _table(names[1:]),
           "row_extra": _table(names + ["hs.nowhere"]),
           "no_table": "# Telemetry\n\nno table here\n"}[case]
    failures = _lint(tmp_path, None, doc)
    if case == "matches":
        assert failures == []
        return
    assert len(failures) == 1, failures
    assert {"row_missing": repr(names[0]), "row_extra": "'hs.nowhere'",
            "no_table": "no device-scope table"}[case] in failures[0]


def test_the_shipped_package_and_docs_pass():
    import hyperspace_tpu

    doc = os.path.join(REPO_ROOT, "docs", "telemetry.md")
    assert check_jit_entry_points(os.path.dirname(hyperspace_tpu.__file__),
                                  doc) == []
    with open(doc, encoding="utf-8") as f:
        assert sorted(device_scope_table(f.read())) == sorted(
            telemetry.DEVICE_SCOPES)


def test_the_seam_refuses_a_scope_the_table_lacks():
    with pytest.raises(ValueError, match="hs.nowhere"):
        telemetry.instrumented_jit("test.unknown_scope", lambda x: x,
                                   scope="hs.nowhere")
    with pytest.raises(TypeError):
        telemetry.instrumented_jit("test.no_scope", lambda x: x)
