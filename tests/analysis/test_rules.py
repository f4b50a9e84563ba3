"""Static-analysis rule tests driven by the known-bad fixtures.

Each fixture under ``fixtures/`` carries ``# BAD: <rule>`` markers on
the exact lines the analyzer must flag.  The tests parse the markers
and assert the finding set matches line-for-line — no extra findings,
no missed ones.
"""
import re
import textwrap
from pathlib import Path

import pytest

from megatron_llm_tpu.analysis import AnalysisConfig, analyze_source

FIXTURES = Path(__file__).parent / "fixtures"
_MARKER = re.compile(r"#\s*BAD:\s*([a-z\-]+)\s*$")


def expected_findings(path: Path):
    """(line, rule) pairs declared by ``# BAD:`` markers in a fixture."""
    out = set()
    for lineno, line in enumerate(path.read_text().splitlines(), start=1):
        m = _MARKER.search(line)
        if m:
            out.add((lineno, m.group(1)))
    return out


def actual_findings(path: Path, config=None):
    findings = analyze_source(str(path), path.read_text(), config or AnalysisConfig())
    return {(f.line, f.rule) for f in findings}


@pytest.mark.parametrize(
    "name,rule",
    [
        ("bad_r1.py", "recompile"),
        ("bad_r2.py", "host-sync"),
        ("bad_r3.py", "donation"),
        ("bad_r4.py", "tracer-leak"),
        ("bad_r5.py", "lock-discipline"),
        ("bad_r6.py", "dequant-hot-path"),
        ("bad_r7.py", "dyn-shape"),
        ("bad_r8.py", "adapter-materialize"),
    ],
)
def test_fixture_findings_exact(name, rule):
    path = FIXTURES / name
    expected = expected_findings(path)
    assert expected, f"{name} has no BAD markers — fixture is broken"
    assert all(r == rule for _, r in expected)
    assert actual_findings(path) == expected


def _analyze(src: str, path="megatron_llm_tpu/serving/snippet.py", config=None):
    return analyze_source(path, textwrap.dedent(src), config or AnalysisConfig())


def test_kernel_functions_are_hot_paths():
    # Functions named *_kernel under kernels/ are hot by construction:
    # host syncs inside them are flagged with no hot-path comment needed.
    src = """
        import numpy as np

        def attn_kernel(q_ref, o_ref):
            np.asarray(q_ref)

        def helper(q_ref):
            np.asarray(q_ref)
    """
    findings = _analyze(src, path="megatron_llm_tpu/kernels/attn.py")
    assert [(f.line, f.rule) for f in findings] == [(5, "host-sync")]


def test_kernel_ref_params_are_traced():
    # In kernels/, *_ref parameters are traced refs: branching on them leaks.
    src = """
        def attn_kernel(q_ref, o_ref, block):
            if q_ref[0] > 0:
                o_ref[0] = 1
            if block > 2:
                o_ref[0] = 2
    """
    findings = _analyze(src, path="megatron_llm_tpu/kernels/attn.py")
    assert [(f.line, f.rule) for f in findings] == [(3, "tracer-leak")]


def test_dequant_flagged_anywhere_in_kernels():
    # In kernels/ every function is on the bytes-bound path: whole-tensor
    # dequant helpers are flagged without any hot-path comment, even in
    # launch builders (only per-tile dequant inside the kernel body keeps
    # the packed form as what streams from HBM).
    src = """
        from megatron_llm_tpu.ops.quant import dequantize_weight

        def _launch(w):
            return dequantize_weight(w)
    """
    findings = _analyze(src, path="megatron_llm_tpu/kernels/flash_decode.py")
    assert [(f.line, f.rule) for f in findings] == [(5, "dequant-hot-path")]


def test_allow_comment_suppresses_finding():
    src = """
        import numpy as np

        # tpulint: hot-path
        def step(tok):
            return np.asarray(tok)  # tpulint: allow[host-sync] the one scheduling point
    """
    assert _analyze(src) == []


def test_allow_comment_above_applies_to_next_line():
    src = """
        import numpy as np

        # tpulint: hot-path
        def step(tok):
            # tpulint: allow[host-sync] deliberate fetch
            return np.asarray(tok)
    """
    assert _analyze(src) == []


def test_allow_wrong_rule_does_not_suppress():
    src = """
        import numpy as np

        # tpulint: hot-path
        def step(tok):
            return np.asarray(tok)  # tpulint: allow[donation] wrong rule
    """
    rules = {f.rule for f in _analyze(src)}
    assert "host-sync" in rules


def test_malformed_directive_is_itself_a_finding():
    src = """
        x = 1  # tpulint: allow[no-such-rule] typo'd rule id
    """
    findings = _analyze(src)
    assert [(f.line, f.rule) for f in findings] == [(2, "suppression")]
    assert "no-such-rule" in findings[0].message


def test_skip_file_silences_everything():
    src = """
        # tpulint: skip-file generated code
        import numpy as np

        # tpulint: hot-path
        def step(tok):
            return np.asarray(tok)
    """
    assert _analyze(src) == []


def test_syntax_error_reported_as_suppression_finding():
    findings = _analyze("def broken(:\n")
    assert len(findings) == 1
    assert findings[0].rule == "suppression"


def test_fingerprint_is_line_free():
    # Baselines must survive unrelated edits shifting line numbers.
    src_a = """
        import numpy as np

        # tpulint: hot-path
        def step(tok):
            return np.asarray(tok)
    """
    src_b = "\n\n\n" + textwrap.dedent(src_a)
    (fa,) = _analyze(src_a)
    (fb,) = analyze_source("megatron_llm_tpu/serving/snippet.py", src_b, AnalysisConfig())
    assert fa.line != fb.line
    assert fa.fingerprint == fb.fingerprint
