"""kernelcheck: the Pallas/TPU kernel-discipline static analyzer (tier-1).

Three layers, mirroring test_tracecheck/test_meshcheck/test_faultcheck:
  1. per-rule fixture tests — a flagged snippet, a clean twin, and a
     pragma-suppressed copy for each KRN rule;
  2. machinery tests — the FOUR-suite pragma-isolation matrix, baseline
     round-trip, shared-parse order independence across all four
     analyzers (kernelcheck first AND last), single-suite + unified CLI
     exit codes, the standalone tools/ loader, and the planner-vs-lint
     geometry agreement (tile_geometry is the single source both
     memwatch's plan_fused_layers and KRN002 derive from);
  3. the package gate — ``paddle_tpu`` analyzed end to end must show
     ZERO findings beyond tools/kernelcheck_baseline.json (checked in
     EMPTY), inside the acceptance time budget.

Pure AST: no jax import required by the analyzer itself.
"""

import json
import os
import subprocess
import sys
import textwrap
import time

import pytest

from paddle_tpu.analysis.kernelcheck import (AnalyzerConfig,
                                             analyze_package,
                                             load_baseline,
                                             subtract_baseline,
                                             write_baseline, KERNEL_RULES)
from paddle_tpu.analysis import faultcheck as fc
from paddle_tpu.analysis import meshcheck as mc
from paddle_tpu.analysis import tracecheck as tc

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PKG = os.path.join(REPO, "paddle_tpu")
BASELINE = os.path.join(REPO, "tools", "kernelcheck_baseline.json")

pytestmark = pytest.mark.kernelcheck


# --------------------------------------------------------------- harness
def run_snippet(tmp_path, source, config=None, name="mod.py", extra=None):
    """Analyze one module as a tiny package; returns the result."""
    pkg = tmp_path / "fixpkg"
    pkg.mkdir(exist_ok=True)
    (pkg / "__init__.py").write_text("")
    (pkg / name).write_text(textwrap.dedent(source))
    for fname, src in (extra or {}).items():
        (pkg / fname).write_text(textwrap.dedent(src))
    result = analyze_package(str(pkg), config)
    assert not result.errors, result.errors
    return result


def codes(result):
    return [f.rule for f in result.findings]


HEADER = """
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
"""


# ---------------------------------------------------------------- KRN001
KRN001_FLAGGED = HEADER + """
    def _kern(x_ref, o_ref):
        o_ref[...] = x_ref[...]

    def _launch(x):
        return pl.pallas_call(
            _kern, grid=(1,),
            in_specs=[pl.BlockSpec((8, 96), lambda i: (i, 0))],
            out_specs=pl.BlockSpec((8, 128), lambda i: (i, 0)),
            out_shape=x)(x)
"""


def test_krn001_lane_misaligned(tmp_path):
    res = run_snippet(tmp_path, KRN001_FLAGGED)
    assert codes(res) == ["KRN001"]
    assert "minor-most dim 96" in res.findings[0].message


def test_krn001_sublane_misaligned(tmp_path):
    res = run_snippet(tmp_path, KRN001_FLAGGED.replace(
        "(8, 96)", "(12, 128)"))
    assert codes(res) == ["KRN001"]
    assert "second-minor dim 12" in res.findings[0].message


def test_krn001_aligned_clean(tmp_path):
    res = run_snippet(tmp_path, KRN001_FLAGGED.replace(
        "(8, 96)", "(16, 256)"))
    assert codes(res) == []


def test_krn001_module_const_resolution(tmp_path):
    # dims resolve through module constants and literal locals — and an
    # UNRESOLVABLE dim (a runtime parameter) makes no claim at all
    res = run_snippet(tmp_path, HEADER + """
    COLS = 100

    def _kern(x_ref, o_ref):
        o_ref[...] = x_ref[...]

    def _launch(x, runtime_cols):
        rows = 8
        return pl.pallas_call(
            _kern, grid=(1,),
            in_specs=[pl.BlockSpec((rows, COLS), lambda i: (i, 0)),
                      pl.BlockSpec((8, runtime_cols),
                                   lambda i: (i, 0))],
            out_specs=pl.BlockSpec((8, 128), lambda i: (i, 0)),
            out_shape=x)(x, x)
    """)
    assert codes(res) == ["KRN001"]
    assert "minor-most dim 100" in res.findings[0].message


def test_krn001_scratch_dtype_aware_smem_exempt(tmp_path):
    # VMEM scratch obeys the dtype's sublane packing (8 rows of int8
    # straddle the 32-sublane tile); SMEM is scalar memory and exempt
    res = run_snippet(tmp_path, HEADER + """
    def _kern(x_ref, o_ref, acc_ref, flag_ref):
        o_ref[...] = x_ref[...]

    def _launch(x):
        return pl.pallas_call(
            _kern, grid=(1,),
            in_specs=[pl.BlockSpec((8, 128), lambda i: (i, 0))],
            out_specs=pl.BlockSpec((8, 128), lambda i: (i, 0)),
            scratch_shapes=[pltpu.VMEM((8, 128), jnp.int8),
                            pltpu.SMEM((1, 3), jnp.int32)],
            out_shape=x)(x)
    """)
    assert codes(res) == ["KRN001"]
    assert "sublane packing 32" in res.findings[0].message


def test_krn001_pragma(tmp_path):
    res = run_snippet(tmp_path, KRN001_FLAGGED.replace(
        "in_specs=[pl.BlockSpec((8, 96), lambda i: (i, 0))],",
        "in_specs=[pl.BlockSpec((8, 96), lambda i: (i, 0))],"
        "  # kernelcheck: disable=KRN001"))
    assert codes(res) == []
    assert len(res.suppressed) == 1


# ---------------------------------------------------------------- KRN002
KRN002_FLAGGED = HEADER + """
    def _kern(x_ref, o_ref):
        o_ref[...] = x_ref[...]

    def _launch(x):
        return pl.pallas_call(
            _kern, grid=(4,),
            in_specs=[pl.BlockSpec((4096, 1024), lambda i: (i, 0))],
            out_specs=pl.BlockSpec((8, 128), lambda i: (i, 0)),
            out_shape=x)(x)
"""


def test_krn002_block_overflow(tmp_path):
    # 4096 x 1024 double-buffered at 4 B is 32 MB — twice the core
    res = run_snippet(tmp_path, KRN002_FLAGGED)
    assert codes(res) == ["KRN002"]
    assert "VMEM bound" in res.findings[0].message


def test_krn002_fitting_blocks_clean(tmp_path):
    res = run_snippet(tmp_path, KRN002_FLAGGED.replace(
        "(4096, 1024)", "(512, 1024)"))
    assert codes(res) == []


def test_krn002_scratch_pushes_over(tmp_path):
    # blocks alone fit (8 MB); persistent f32 scratch tips the set over
    src = KRN002_FLAGGED.replace(
        "(4096, 1024)", "(1024, 1024)").replace(
        "out_shape=x)(x)",
        "scratch_shapes=[pltpu.VMEM((2048, 1024), jnp.float32)],\n"
        "            out_shape=x)(x)").replace(
        "def _kern(x_ref, o_ref):",
        "def _kern(x_ref, o_ref, acc_ref):")
    res = run_snippet(tmp_path, src)
    assert codes(res) == ["KRN002"]
    res = run_snippet(tmp_path, src.replace(
        "pltpu.VMEM((2048, 1024), jnp.float32)",
        "pltpu.VMEM((1024, 1024), jnp.float32)"))
    assert codes(res) == []


KRN002_TEMPLATE_OK = HEADER + """
    LANES = 128

    def _kern(x_ref, o_ref, *refs):
        o_ref[...] = x_ref[...]

    def fused_block_decode_ref(x):
        return x

    def fused_block_decode_pallas(x, b_pad, hidden, nh, nkv, inter,
                                  tc_max, rep_rows, d):
        return pl.pallas_call(
            _kern, grid=(2,),
            in_specs=[pl.BlockSpec((8, 128), lambda i: (i, 0))],
            out_specs=pl.BlockSpec((8, 128), lambda i: (i, 0)),
            scratch_shapes=[
                pltpu.VMEM((b_pad, hidden), jnp.float32),
                pltpu.VMEM((nh, b_pad, d), jnp.float32),
                pltpu.VMEM((nkv, b_pad, d), jnp.float32),
                pltpu.VMEM((nkv, b_pad, d), jnp.float32),
                pltpu.VMEM((nh, b_pad, d), jnp.float32),
                pltpu.VMEM((b_pad, hidden), jnp.float32),
                pltpu.VMEM((b_pad, inter), jnp.float32),
                pltpu.VMEM((b_pad, tc_max), jnp.float32),
                pltpu.VMEM((b_pad, tc_max), jnp.float32),
                pltpu.VMEM((rep_rows, d), jnp.float32),
                pltpu.VMEM((rep_rows, LANES), jnp.float32),
                pltpu.VMEM((rep_rows, LANES), jnp.float32),
            ],
            out_shape=x)(x)
"""


def test_krn002_template_match_clean(tmp_path):
    # a kernel spelling exactly the shared single-layer template passes
    res = run_snippet(tmp_path, KRN002_TEMPLATE_OK)
    assert codes(res) == []


def test_krn002_template_drift_flagged(tmp_path):
    # drop one carry: the extracted multiset no longer matches the
    # template memwatch prices from — the drift fires regardless of
    # whether any dim resolves to an integer
    res = run_snippet(tmp_path, KRN002_TEMPLATE_OK.replace(
        "                pltpu.VMEM((b_pad, inter), jnp.float32),\n", ""))
    assert codes(res) == ["KRN002"]
    assert "plan_fused_layers" in res.findings[0].message
    assert "inter" in res.findings[0].message


# ---------------------------------------------------------------- KRN003
KRN003_FLAGGED = HEADER + """
    def _kern(x_ref, o_ref):
        o_ref[...] = x_ref[...]

    def _launch(x, n, block):
        return pl.pallas_call(
            _kern, grid=(n // block,),
            in_specs=[pl.BlockSpec((8, 128), lambda i: (i, 0))],
            out_specs=pl.BlockSpec((8, 128), lambda i: (i, 0)),
            out_shape=x)(x)
"""


def test_krn003_raw_floordiv_grid(tmp_path):
    res = run_snippet(tmp_path, KRN003_FLAGGED)
    assert codes(res) == ["KRN003"]
    assert "ragged final tile" in res.findings[0].message


def test_krn003_ceil_div_clean(tmp_path):
    res = run_snippet(tmp_path, KRN003_FLAGGED.replace(
        "grid=(n // block,)", "grid=(-(-n // block),)"))
    assert codes(res) == []
    res = run_snippet(tmp_path, KRN003_FLAGGED.replace(
        "grid=(n // block,)", "grid=(pl.cdiv(n, block),)"))
    assert codes(res) == []


def test_krn003_divisibility_guard_clean(tmp_path):
    res = run_snippet(tmp_path, KRN003_FLAGGED.replace(
        "return pl.pallas_call(",
        "assert n % block == 0\n"
        "        return pl.pallas_call("))
    assert codes(res) == []


def test_krn003_index_map_arity_mismatch(tmp_path):
    res = run_snippet(tmp_path, KRN003_FLAGGED.replace(
        "grid=(n // block,)", "grid=(4, 4)").replace(
        "in_specs=[pl.BlockSpec((8, 128), lambda i: (i, 0))],",
        "in_specs=[pl.BlockSpec((8, 128), lambda i, j: (i, j))],"))
    assert codes(res) == ["KRN003"]
    assert "grid rank" in res.findings[0].message


def test_krn003_prefetch_counts_toward_arity(tmp_path):
    # PrefetchScalarGridSpec chased through a local name: maps take one
    # extra leading ref per prefetch operand
    src = HEADER + """
    def _kern(t_ref, x_ref, o_ref):
        o_ref[...] = x_ref[...]

    def _launch(x, table):
        spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(4,),
            in_specs=[pl.BlockSpec((8, 128), lambda s, i: (i, 0))],
            out_specs=pl.BlockSpec((8, 128), lambda s, i: (i, 0)))
        return pl.pallas_call(
            _kern, grid_spec=spec, out_shape=x)(table, x)
    """
    assert codes(run_snippet(tmp_path, src)) == []
    res = run_snippet(tmp_path, src.replace(
        "in_specs=[pl.BlockSpec((8, 128), lambda s, i: (i, 0))],",
        "in_specs=[pl.BlockSpec((8, 128), lambda i: (i, 0))],"))
    assert codes(res) == ["KRN003"]
    assert "num_scalar_prefetch is 2" in res.findings[0].message


def test_krn003_element_offset_return(tmp_path):
    # multiplying by the spec's own block dim double-scales the offset
    res = run_snippet(tmp_path, HEADER + """
    BLOCK = 256

    def _kern(x_ref, o_ref):
        o_ref[...] = x_ref[...]

    def _launch(x):
        return pl.pallas_call(
            _kern, grid=(4,),
            in_specs=[pl.BlockSpec((BLOCK, 128),
                                   lambda i: (i * BLOCK, 0))],
            out_specs=pl.BlockSpec((8, 128), lambda i: (i, 0)),
            out_shape=x)(x)
    """)
    assert codes(res) == ["KRN003"]
    assert "BLOCK indices" in res.findings[0].message


def test_krn003_pragma(tmp_path):
    res = run_snippet(tmp_path, KRN003_FLAGGED.replace(
        "grid=(n // block,),",
        "grid=(n // block,),  # kernelcheck: disable=KRN003"))
    assert codes(res) == []
    assert len(res.suppressed) == 1


# ---------------------------------------------------------------- KRN004
KRN004_FLAGGED = HEADER + """
    def _kern(x_ref, o_ref):
        while o_ref[0, 0] < 4:
            o_ref[0, 0] = o_ref[0, 0] + 1

    def _launch(x):
        return pl.pallas_call(_kern, grid=(1,), out_shape=x)(x)
"""


def test_krn004_while_in_kernel(tmp_path):
    res = run_snippet(tmp_path, KRN004_FLAGGED)
    assert codes(res) == ["KRN004"]
    assert "while" in res.findings[0].message


def test_krn004_plain_function_while_clean(tmp_path):
    # the same while OUTSIDE any kernel body is not this suite's business
    res = run_snippet(tmp_path, """
        def spin(n):
            while n > 0:
                n -= 1
            return n
    """)
    assert codes(res) == []


def test_krn004_host_call_through_helper(tmp_path):
    # the closure walk: a same-module helper called from the kernel body
    # carries its host calls into the kernel's findings
    res = run_snippet(tmp_path, HEADER + """
    import time

    def _now():
        return time.time()

    def _kern(x_ref, o_ref):
        o_ref[...] = x_ref[...] * _now()

    def _launch(x):
        return pl.pallas_call(_kern, grid=(1,), out_shape=x)(x)
    """)
    assert codes(res) == ["KRN004"]
    assert "host-module call" in res.findings[0].message


def test_krn004_mosaic_unsupported_jnp(tmp_path):
    res = run_snippet(tmp_path, KRN004_FLAGGED.replace(
        "        while o_ref[0, 0] < 4:\n"
        "            o_ref[0, 0] = o_ref[0, 0] + 1",
        "        o_ref[...] = jnp.sort(x_ref[...])"))
    assert codes(res) == ["KRN004"]
    assert "no Mosaic lowering" in res.findings[0].message


def test_krn004_static_unroll_clean(tmp_path):
    res = run_snippet(tmp_path, KRN004_FLAGGED.replace(
        "        while o_ref[0, 0] < 4:\n"
        "            o_ref[0, 0] = o_ref[0, 0] + 1",
        "        for i in range(4):\n"
        "            o_ref[i, :] = jnp.exp(x_ref[i, :])"))
    assert codes(res) == []


def test_krn004_kernel_resolved_through_partial(tmp_path):
    res = run_snippet(tmp_path, HEADER + """
    import functools

    def _kern(x_ref, o_ref, *, steps):
        while steps > 0:
            steps -= 1

    def _launch(x):
        k = functools.partial(_kern, steps=2)
        return pl.pallas_call(k, grid=(1,), out_shape=x)(x)
    """)
    assert codes(res) == ["KRN004"]


def test_krn004_pragma(tmp_path):
    res = run_snippet(tmp_path, KRN004_FLAGGED.replace(
        "while o_ref[0, 0] < 4:",
        "while o_ref[0, 0] < 4:  # kernelcheck: disable=KRN004"))
    assert codes(res) == []


# ---------------------------------------------------------------- KRN005
def test_krn005_low_precision_scratch(tmp_path):
    res = run_snippet(tmp_path, HEADER + """
    def _kern(x_ref, o_ref, acc_ref):
        o_ref[...] = x_ref[...]

    def _launch(x):
        return pl.pallas_call(
            _kern, grid=(4,),
            in_specs=[pl.BlockSpec((8, 128), lambda i: (i, 0))],
            out_specs=pl.BlockSpec((8, 128), lambda i: (i, 0)),
            scratch_shapes=[pltpu.VMEM((16, 128), jnp.bfloat16)],
            out_shape=x)(x)
    """)
    assert codes(res) == ["KRN005"]
    assert "bf16" in res.findings[0].message or \
        "bfloat16" in res.findings[0].message


KRN005_CARRY = HEADER + """
    def _kern(x_ref, o_ref, acc_ref):
        acc_ref[...] += x_ref[...]
        o_ref[...] = acc_ref[...]

    def _launch(x):
        return pl.pallas_call(
            _kern, grid=(4,),
            in_specs=[pl.BlockSpec((8, 128), lambda i: (i, 0))],
            out_specs=pl.BlockSpec((8, 128), lambda i: (i, 0)),
            scratch_shapes=[pltpu.VMEM((8, 128), jnp.float32)],
            out_shape=x)(x)
"""


def test_krn005_carry_without_init(tmp_path):
    res = run_snippet(tmp_path, KRN005_CARRY)
    assert codes(res) == ["KRN005"]
    assert "stale" in res.findings[0].message


def test_krn005_when_guarded_init_clean(tmp_path):
    res = run_snippet(tmp_path, KRN005_CARRY.replace(
        "        acc_ref[...] += x_ref[...]",
        "        @pl.when(pl.program_id(0) == 0)\n"
        "        def _init():\n"
        "            acc_ref[...] = x_ref[...] * 0.0\n"
        "        acc_ref[...] += x_ref[...]"))
    assert codes(res) == []


KRN005_DOT = HEADER + """
    def _kern(x_ref, w_ref, o_ref):
        o_ref[...] = jnp.dot(x_ref[...], w_ref[...])

    def _launch(x, w):
        return pl.pallas_call(_kern, grid=(1,), out_shape=x)(x, w)
"""


def test_krn005_unpinned_dot(tmp_path):
    res = run_snippet(tmp_path, KRN005_DOT)
    assert codes(res) == ["KRN005"]
    assert "preferred_element_type" in res.findings[0].message


def test_krn005_matmult_operator(tmp_path):
    res = run_snippet(tmp_path, KRN005_DOT.replace(
        "jnp.dot(x_ref[...], w_ref[...])",
        "x_ref[...] @ w_ref[...]"))
    assert codes(res) == ["KRN005"]
    assert "`@` matmul" in res.findings[0].message


def test_krn005_pinned_dot_clean(tmp_path):
    res = run_snippet(tmp_path, KRN005_DOT.replace(
        "jnp.dot(x_ref[...], w_ref[...])",
        "jnp.dot(x_ref[...], w_ref[...],\n"
        "                            preferred_element_type=jnp.float32)"))
    assert codes(res) == []


def test_krn005_pragma(tmp_path):
    res = run_snippet(tmp_path, KRN005_CARRY.replace(
        "        return pl.pallas_call(",
        "        # kernelcheck: disable=KRN005\n"
        "        return pl.pallas_call("))
    assert codes(res) == []
    assert len(res.suppressed) == 1


# ---------------------------------------------------------------- KRN006
KRN006_FLAGGED = HEADER + """
    def _kern(x_ref, o_ref):
        o_ref[...] = x_ref[...]

    def softmax_pallas(x):
        return pl.pallas_call(_kern, grid=(1,), out_shape=x)(x)
"""


def test_krn006_missing_ref_twin(tmp_path):
    res = run_snippet(tmp_path, KRN006_FLAGGED)
    assert codes(res) == ["KRN006"]
    assert "softmax_ref" in res.findings[0].message


def test_krn006_ref_twin_clean(tmp_path):
    res = run_snippet(tmp_path, KRN006_FLAGGED + """
    def softmax_ref(x):
        return x
    """)
    assert codes(res) == []


def test_krn006_prefix_covers_variants(tmp_path):
    # one softmax_ref oracle covers softmax_with_stats_pallas too (the
    # flash_attention_ref / flash_attention_with_lse convention)
    res = run_snippet(tmp_path, KRN006_FLAGGED + """
    def softmax_with_stats_pallas(x):
        return softmax_pallas(x)

    def softmax_ref(x):
        return x
    """)
    assert codes(res) == []


def test_krn006_private_entry_exempt(tmp_path):
    res = run_snippet(tmp_path, KRN006_FLAGGED.replace(
        "def softmax_pallas(x):", "def _softmax_pallas(x):"))
    assert codes(res) == []


def test_krn006_transitive_public_caller(tmp_path):
    # a public wrapper reaching the site through a private launcher is
    # an entry point too — the census is transitive within the module
    res = run_snippet(tmp_path, KRN006_FLAGGED.replace(
        "def softmax_pallas(x):", "def _softmax_impl(x):") + """
    def softmax(x):
        return _softmax_impl(x)
    """)
    assert codes(res) == ["KRN006"]
    assert res.findings[0].func == "softmax"


def test_krn006_pragma(tmp_path):
    res = run_snippet(tmp_path, KRN006_FLAGGED.replace(
        "def softmax_pallas(x):",
        "def softmax_pallas(x):  # kernelcheck: disable=KRN006"))
    assert codes(res) == []


# ---------------------------------------------------- machinery / parse
def test_rule_catalogue_complete():
    assert set(KERNEL_RULES) == {"KRN001", "KRN002", "KRN003", "KRN004",
                                 "KRN005", "KRN006"}
    assert set(AnalyzerConfig().rules) == set(KERNEL_RULES)


# one module that trips all FOUR suites at once: TRC001 (flag read
# under trace), MSH001 (unbound collective axis), FLT004 (unbounded
# retry loop), KRN001 (off-grid BlockSpec)
QUAD_SOURCE = """
    import time
    import jax
    from jax import lax
    from jax.experimental import pallas as pl
    from .flags import get_flag

    def kernel(x):
        return x * get_flag("use_pallas")

    step = jax.jit(kernel)

    def bad_axis(x):
        return lax.psum(x, "tp")

    def forever(dispatch):
        while True:
            try:
                return dispatch()
            except RuntimeError:
                time.sleep(0.1)

    def misaligned_ref(x):
        return x

    def misaligned(x):
        return pl.pallas_call(
            lambda x_ref, o_ref: None,
            grid=(1,),
            in_specs=[pl.BlockSpec((8, 96), lambda i: (i, 0))],
            out_specs=pl.BlockSpec((8, 128), lambda i: (i, 0)),
            out_shape=x)(x)
"""

_QUAD_LINES = {
    "tracecheck": ('return x * get_flag("use_pallas")', "TRC001"),
    "meshcheck": ('return lax.psum(x, "tp")', "MSH001"),
    "faultcheck": ("time.sleep(0.1)", "FLT004"),
    "kernelcheck": ("in_specs=[pl.BlockSpec((8, 96), lambda i: (i, 0))],",
                    "KRN001"),
}


def _quad_results(tmp_path, source):
    pkg = tmp_path / "fixpkg"
    pkg.mkdir(exist_ok=True)
    (pkg / "__init__.py").write_text("")
    (pkg / "mod.py").write_text(textwrap.dedent(source))
    return {
        "tracecheck": tc.analyze_package(str(pkg)),
        "meshcheck": mc.analyze_package(str(pkg)),
        "faultcheck": fc.analyze_package(str(pkg)),
        "kernelcheck": analyze_package(str(pkg)),
    }


def test_four_suite_pragma_isolation_matrix(tmp_path):
    """Every suite's pragma silences ONLY its own rule: a 4x4 matrix
    over one module that trips TRC001 + MSH001 + FLT004 + KRN001."""
    base = {s: [f.rule for f in r.findings]
            for s, r in _quad_results(tmp_path, QUAD_SOURCE).items()}
    assert base == {"tracecheck": ["TRC001"], "meshcheck": ["MSH001"],
                    "faultcheck": ["FLT004"], "kernelcheck": ["KRN001"]}

    for pragma_tool in _QUAD_LINES:
        src = QUAD_SOURCE
        for target_suite, (line, rule) in _QUAD_LINES.items():
            src = src.replace(
                line, f"{line}  # {pragma_tool}: disable={rule}")
        results = _quad_results(tmp_path, src)
        for suite, (_, rule) in _QUAD_LINES.items():
            found = [f.rule for f in results[suite].findings]
            if suite == pragma_tool:
                assert found == [], (pragma_tool, suite, found)
                assert len(results[suite].suppressed) == 1
            else:
                # the foreign pragma (even naming this suite's rule
                # code) must not silence this suite
                assert found == [rule], (pragma_tool, suite, found)


def test_foreign_pragma_with_own_code_does_not_silence(tmp_path):
    # a tracecheck pragma spelling a KRN code still never crosses suites
    res = run_snippet(tmp_path, KRN001_FLAGGED.replace(
        "in_specs=[pl.BlockSpec((8, 96), lambda i: (i, 0))],",
        "in_specs=[pl.BlockSpec((8, 96), lambda i: (i, 0))],"
        "  # tracecheck: disable=KRN001"))
    assert codes(res) == ["KRN001"]


def test_baseline_round_trip_stable(tmp_path):
    pkg = tmp_path / "fixpkg"
    pkg.mkdir()
    (pkg / "__init__.py").write_text("")
    (pkg / "mod.py").write_text(textwrap.dedent(KRN001_FLAGGED))
    res = analyze_package(str(pkg))
    assert res.findings

    b1 = tmp_path / "baseline.json"
    entries1 = write_baseline(str(b1), res.findings)
    assert entries1 == sorted(entries1)
    new, leftovers = subtract_baseline(
        analyze_package(str(pkg)).findings, load_baseline(str(b1)))
    assert new == [] and not leftovers

    # line-number stability: shift every finding down — fingerprints hold
    (pkg / "mod.py").write_text(
        "X = 1\nY = 2\n\n" + textwrap.dedent(KRN001_FLAGGED))
    new, leftovers = subtract_baseline(
        analyze_package(str(pkg)).findings, load_baseline(str(b1)))
    assert new == [] and not leftovers


def test_baseline_multiset_semantics(tmp_path):
    # two textually identical misaligned specs in one function: one
    # baselined entry forgives exactly one of them
    src = KRN001_FLAGGED.replace(
        "in_specs=[pl.BlockSpec((8, 96), lambda i: (i, 0))],",
        "in_specs=[pl.BlockSpec((8, 96), lambda i: (i, 0)),\n"
        "                      pl.BlockSpec((8, 96), lambda i: (i, 0))],")
    pkg = tmp_path / "fixpkg"
    pkg.mkdir()
    (pkg / "__init__.py").write_text("")
    (pkg / "mod.py").write_text(textwrap.dedent(src))
    findings = analyze_package(str(pkg)).findings
    assert len(findings) == 2
    b = tmp_path / "baseline.json"
    write_baseline(str(b), findings[:1])
    new, _ = subtract_baseline(findings, load_baseline(str(b)))
    assert len(new) == 1


def test_shared_parse_order_independence():
    """All FOUR suites over ONE parse must report exactly what they
    report standalone, with kernelcheck running first AND last — its
    context build is a pure read of the shared ModuleInfos."""
    kc_alone = analyze_package(PKG)
    tc_alone = tc.analyze_package(PKG)
    mc_alone = mc.analyze_package(PKG)
    fc_alone = fc.analyze_package(PKG)

    parsed = tc.parse_package(PKG)
    kc_first = analyze_package(PKG, parsed=parsed)
    tc_mid = tc.analyze_package(PKG, parsed=parsed)
    mc_mid = mc.analyze_package(PKG, parsed=parsed)
    fc_last = fc.analyze_package(PKG, parsed=parsed)

    parsed2 = tc.parse_package(PKG)
    tc_first = tc.analyze_package(PKG, parsed=parsed2)
    mc_mid2 = mc.analyze_package(PKG, parsed=parsed2)
    fc_mid = fc.analyze_package(PKG, parsed=parsed2)
    kc_last = analyze_package(PKG, parsed=parsed2)

    def sig(res):
        return [f.format() for f in res.findings]

    assert sig(kc_first) == sig(kc_alone) == sig(kc_last)
    assert sig(tc_mid) == sig(tc_alone) == sig(tc_first)
    assert sig(mc_mid) == sig(mc_alone) == sig(mc_mid2)
    assert sig(fc_last) == sig(fc_alone) == sig(fc_mid)
    # geometry census counters must be order-independent too
    for a, b in ((kc_first, kc_alone), (kc_last, kc_alone)):
        assert (a.n_sites, a.n_specs, a.n_scratch, a.n_kernels) == \
            (b.n_sites, b.n_specs, b.n_scratch, b.n_kernels)
    assert tc_first.n_traced == tc_alone.n_traced


def test_exclude_patterns_apply_to_shared_parse(tmp_path):
    pkg = tmp_path / "fixpkg"
    pkg.mkdir()
    (pkg / "__init__.py").write_text("")
    (pkg / "mod.py").write_text(textwrap.dedent(KRN001_FLAGGED))
    parsed = tc.parse_package(str(pkg))
    cfg = AnalyzerConfig(exclude_patterns=("mod.py",))
    assert analyze_package(str(pkg), cfg, parsed=parsed).findings == []
    assert analyze_package(str(pkg), cfg).findings == []


# ------------------------------------------------------------------- CLI
def test_single_suite_cli_exit_codes(tmp_path, capsys):
    from paddle_tpu.analysis.kernelcheck import cli

    pkg = tmp_path / "fixpkg"
    pkg.mkdir()
    (pkg / "__init__.py").write_text("")
    (pkg / "mod.py").write_text(textwrap.dedent(KRN001_FLAGGED))

    # a rule-filtered run must never write the baseline (it would
    # clobber the other rules' entries)
    rc = cli.main([str(pkg), "--rules", "KRN001", "--update-baseline"])
    assert rc == 2
    assert "clobber" in capsys.readouterr().err

    rc = cli.main([str(pkg), "--no-baseline"])
    assert rc == 1
    assert "KRN001" in capsys.readouterr().out

    # the --json payload carries the geometry census alongside findings
    rc = cli.main([str(pkg), "--no-baseline", "--json"])
    assert rc == 1
    payload = json.loads(capsys.readouterr().out)
    assert [f["rule"] for f in payload["findings"]] == ["KRN001"]
    assert payload["pallas_sites"] == 1
    assert payload["block_specs"] == 2

    rc = cli.main([str(pkg), "--rules", "KRN004", "--no-baseline"])
    assert rc == 0          # KRN001 not selected
    capsys.readouterr()

    bl = tmp_path / "bl.json"
    rc = cli.main([str(pkg), "--update-baseline", "--baseline", str(bl)])
    assert rc == 0 and bl.exists()
    capsys.readouterr()
    rc = cli.main([str(pkg), "--baseline", str(bl)])
    assert rc == 0
    capsys.readouterr()

    rc = cli.main(["--list-rules"])
    assert rc == 0
    assert "KRN006" in capsys.readouterr().out

    rc = cli.main([str(tmp_path / "nope")])
    assert rc == 2
    capsys.readouterr()


def test_standalone_tools_loader(tmp_path):
    # tools/kernelcheck.py must run as a plain script (no package
    # install, no jax import) and exit 1 on a finding
    pkg = tmp_path / "fixpkg"
    pkg.mkdir()
    (pkg / "__init__.py").write_text("")
    (pkg / "mod.py").write_text(textwrap.dedent(KRN001_FLAGGED))
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "kernelcheck.py"),
         str(pkg), "--no-baseline"],
        capture_output=True, text=True)
    assert r.returncode == 1, r.stdout + r.stderr
    assert "KRN001" in r.stdout


def _write_quad_pkg(tmp_path):
    pkg = tmp_path / "fixpkg"
    pkg.mkdir()
    (pkg / "__init__.py").write_text("")
    (pkg / "mod.py").write_text(textwrap.dedent(QUAD_SOURCE))
    (tmp_path / "tools").mkdir()
    return pkg


def test_unified_cli_four_suites_and_formats(tmp_path):
    pkg = _write_quad_pkg(tmp_path)
    env = dict(os.environ, PYTHONPATH=REPO)
    cli = [sys.executable, os.path.join(REPO, "tools", "analyze.py")]

    r = subprocess.run(cli + [str(pkg), "--no-baseline", "--json"],
                       capture_output=True, text=True, env=env)
    assert r.returncode == 1, r.stdout + r.stderr
    payload = json.loads(r.stdout)
    want = {"tracecheck": "TRC001", "meshcheck": "MSH001",
            "faultcheck": "FLT004", "kernelcheck": "KRN001"}
    for suite, rule in want.items():
        assert [f["rule"] for f in payload[suite]["findings"]] == [rule]

    # --suite kernelcheck runs ONLY the KRN rules
    r = subprocess.run(cli + [str(pkg), "--suite", "kernelcheck",
                              "--no-baseline"],
                       capture_output=True, text=True, env=env)
    assert r.returncode == 1
    assert "KRN001" in r.stdout
    assert all(c not in r.stdout for c in ("TRC001", "MSH001", "FLT004"))

    # SARIF: valid JSON, one run, all four suites' results present
    r = subprocess.run(cli + [str(pkg), "--no-baseline", "--format",
                              "sarif"],
                       capture_output=True, text=True, env=env)
    assert r.returncode == 1
    sarif = json.loads(r.stdout)
    assert sarif["version"] == "2.1.0"
    results = sarif["runs"][0]["results"]
    assert {res["ruleId"] for res in results} == \
        {"TRC001", "MSH001", "FLT004", "KRN001"}
    rule_ids = {rule["id"] for rule in
                sarif["runs"][0]["tool"]["driver"]["rules"]}
    assert {"TRC001", "MSH001", "FLT004", "KRN001"} <= rule_ids

    # github annotations: one ::error line per finding
    r = subprocess.run(cli + [str(pkg), "--no-baseline", "--format",
                              "github"],
                       capture_output=True, text=True, env=env)
    assert r.returncode == 1
    lines = [l for l in r.stdout.splitlines() if l.startswith("::error")]
    assert len(lines) == 4
    assert any("title=KRN001" in l and "file=" in l and "line=" in l
               for l in lines)

    # --update-baseline writes all four, then the gate is clean
    r = subprocess.run(cli + [str(pkg), "--update-baseline"],
                       capture_output=True, text=True, env=env)
    assert r.returncode == 0, r.stdout + r.stderr
    for suite in ("tracecheck", "meshcheck", "faultcheck", "kernelcheck"):
        assert (tmp_path / "tools" / f"{suite}_baseline.json").exists()
    r = subprocess.run(cli + [str(pkg)], capture_output=True, text=True,
                       env=env)
    assert r.returncode == 0, r.stdout + r.stderr


def test_unified_cli_changed_only_covers_kernelcheck(tmp_path):
    pkg = _write_quad_pkg(tmp_path)
    env = dict(os.environ, PYTHONPATH=REPO)
    cli = [sys.executable, os.path.join(REPO, "tools", "analyze.py")]
    git = ["git", "-C", str(tmp_path), "-c", "user.email=t@t",
           "-c", "user.name=t"]
    subprocess.run(git[:3] + ["init", "-q"], check=True,
                   capture_output=True)
    subprocess.run(git + ["add", "-A"], check=True, capture_output=True)
    subprocess.run(git + ["commit", "-qm", "seed"], check=True,
                   capture_output=True)

    # nothing changed: the diff-scoped report is empty and exits 0
    r = subprocess.run(cli + [str(pkg), "--no-baseline",
                              "--changed-only", "--json"],
                       capture_output=True, text=True, env=env)
    assert r.returncode == 0, r.stdout + r.stderr
    payload = json.loads(r.stdout)
    assert payload["kernelcheck"]["findings"] == []

    # touch the file: the KRN finding reports alongside the other suites
    (pkg / "mod.py").write_text(
        textwrap.dedent(QUAD_SOURCE) + "\nX = 1\n")
    r = subprocess.run(cli + [str(pkg), "--no-baseline",
                              "--changed-only", "--json"],
                       capture_output=True, text=True, env=env)
    assert r.returncode == 1
    payload = json.loads(r.stdout)
    assert [f["rule"] for f in payload["kernelcheck"]["findings"]] == \
        ["KRN001"]


# ----------------------------------------- planner-vs-lint agreement
def test_planner_and_lint_price_from_one_geometry():
    """memwatch's plan_fused_layers and KRN002 derive from the SAME
    tile_geometry templates: the planner's breakdown must equal
    price_fused_decode on the same env, term for term."""
    from paddle_tpu.analysis.tile_geometry import (fused_decode_env,
                                                   price_fused_decode)
    from paddle_tpu.observability.memory import ModelDims, \
        plan_fused_layers

    dims = ModelDims(hidden=4096, layers=32, heads=32, kv_heads=8,
                     intermediate=11008, vocab=32000)
    env = fused_decode_env(hidden=4096, intermediate=11008, heads=32,
                           kv_heads=8, head_dim=dims.head_dim,
                           batch=8, page_size=64)
    for n in (1, 4, 13):
        plan = plan_fused_layers(dims, fused_layers=n)
        priced = price_fused_decode(env, fused_layers=n)
        assert plan["total"] == priced["total"]
        assert plan["fits"] == priced["fits"]
        for term in ("weight_stream_buffers", "activation_io_buffers",
                     "kv_page_buffers", "scratch"):
            assert plan["breakdown"][term] == priced[term], term
    # only the per-layer KV page term scales with N
    p1 = plan_fused_layers(dims, fused_layers=1)["breakdown"]
    p4 = plan_fused_layers(dims, fused_layers=4)["breakdown"]
    assert p4["kv_page_buffers"] == 4 * p1["kv_page_buffers"]
    assert p4["scratch"] == p1["scratch"]
    assert p4["weight_stream_buffers"] == p1["weight_stream_buffers"]


def test_lint_agrees_with_real_kernel_scratch():
    """The KRN002 template arm extracted from the REAL fused decode
    kernels' source matches tile_geometry's templates — the in-tree
    proof that kernel, planner, and lint share one geometry."""
    cfg = AnalyzerConfig(rules=("KRN002",))
    result = analyze_package(PKG, cfg)
    assert not result.errors, result.errors
    drift = [f for f in result.findings if "drifted" in f.message]
    assert drift == [], "\n".join(f.format() for f in drift)
    # ... and the kernels it checks are actually in the census
    assert result.n_sites >= 10


# ------------------------------------------------------- the tier-1 gate
def test_package_gate_zero_new_findings():
    """THE gate: the whole package against the checked-in baseline —
    which is EMPTY by construction (every real finding was fixed or
    pragma'd with a reason in r18); any new finding fails tier-1."""
    t0 = time.time()
    result = analyze_package(PKG)
    elapsed = time.time() - t0
    assert not result.errors, result.errors

    baseline = load_baseline(BASELINE)
    assert not baseline, "kernelcheck's baseline must stay EMPTY"
    new, leftovers = subtract_baseline(result.findings, baseline)
    assert new == [], (
        "kernelcheck found NEW kernel-discipline findings:\n"
        + "\n".join(f.format() for f in new)
        + "\n\nfix them or add a '# kernelcheck: disable=KRN00x' pragma "
          "with a reason — do NOT baseline kernel findings")
    assert not leftovers
    assert elapsed < 15.0, f"kernelcheck took {elapsed:.1f}s"


def test_package_gate_scale_sanity():
    """Coverage floor: if site extraction silently breaks the gate
    would pass vacuously.  Lower bounds, not exact counts."""
    result = analyze_package(PKG)
    assert result.n_files > 150
    assert result.n_functions > 2000
    assert result.n_sites >= 10       # real pallas_call sites walked
    assert result.n_specs >= 80       # BlockSpec census
    assert result.n_scratch >= 30     # VMEM/SMEM scratch census
    assert result.n_kernels >= 9      # kernel bodies resolved
    # the deliberate scalar/stat-column exemplars stay pragma'd with a
    # reason, which proves KRN001 walks the real kernels
    assert len(result.suppressed) >= 8
