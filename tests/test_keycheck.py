"""keycheck: the compiled-program identity & cache-key soundness
analyzer (tier-1).

Three layers, mirroring the five sibling lint suites:
  1. per-rule fixture tests — a flagged snippet, a clean twin, and a
     pragma-suppressed copy for each KEY rule, plus the minter /
     vocabulary-extraction machinery the rules lean on;
  2. machinery tests — the SIX-suite pragma-isolation matrix, the
     flags.py/key_vocab.py no-drift assertions, baseline round-trip,
     shared-parse order independence across all six analyzers
     (keycheck first AND last), single-suite + unified CLI exit codes,
     and the standalone tools/ loader;
  3. the package gate — ``paddle_tpu`` analyzed end to end must show
     ZERO findings beyond tools/keycheck_baseline.json (checked in
     EMPTY: the real findings this suite surfaced were FIXED, not
     baselined), inside the acceptance time budget, with the key
     census at its expected scale (a silent census collapse would pass
     the gate vacuously).

The dynamic twin lives in tests/test_key_matrix.py: the lattice of
engine configs whose DecodeKeys this suite reasons about statically is
exercised there at runtime (distinct configs => distinct keys,
eager-flag toggles => identical keys, PROGRAM_FLAGS toggles => every
key changes).

Pure AST: no jax import required by the analyzer itself.
"""

import json
import os
import subprocess
import sys
import textwrap
import time

import pytest

from paddle_tpu.analysis import key_vocab
from paddle_tpu.analysis.keycheck import (AnalyzerConfig, KEY_RULES,
                                          analyze_package, load_baseline,
                                          subtract_baseline,
                                          write_baseline)
from paddle_tpu.analysis.keycheck import key_model as km
from paddle_tpu.analysis.keycheck import rules as kr
from paddle_tpu.analysis.statecheck import bundle_vocab as bv
from paddle_tpu.analysis import faultcheck as fc
from paddle_tpu.analysis import kernelcheck as kn
from paddle_tpu.analysis import meshcheck as mc
from paddle_tpu.analysis import statecheck as sc
from paddle_tpu.analysis import tracecheck as tc

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PKG = os.path.join(REPO, "paddle_tpu")
BASELINE = os.path.join(REPO, "tools", "keycheck_baseline.json")

pytestmark = pytest.mark.keycheck


# --------------------------------------------------------------- harness
def run_snippet(tmp_path, source, config=None, name="mod.py", extra=None):
    """Analyze one module as a tiny package; extra file keys may carry
    '/' (a fixture's own analysis/key_vocab.py)."""
    pkg = tmp_path / "fixpkg"
    pkg.mkdir(exist_ok=True)
    (pkg / "__init__.py").write_text("")
    (pkg / name).write_text(textwrap.dedent(source))
    for fname, src in (extra or {}).items():
        dest = pkg / fname
        if "/" in fname:
            dest.parent.mkdir(parents=True, exist_ok=True)
            (dest.parent / "__init__.py").write_text("")
        dest.write_text(textwrap.dedent(src))
    result = analyze_package(str(pkg), config)
    assert not result.errors, result.errors
    return result


def codes(result):
    return [f.rule for f in result.findings]


# ---------------------------------------------------------------- KEY001
KEY001_FLAGGED = """
    from .program_cache import decode_program_cache
    from .flags import get_flag


    def _build(note_trace):
        def step(x):
            return x * get_flag("log_level")
        return step


    def admit(key):
        return decode_program_cache().get(key, _build)
"""


def test_key001_untracked_flag_read_in_builder(tmp_path):
    res = run_snippet(tmp_path, KEY001_FLAGGED)
    assert codes(res) == ["KEY001"]
    assert "log_level" in res.findings[0].message
    assert res.findings[0].func == "_build.step"


def test_key001_program_flag_clean(tmp_path):
    # a flag that rides the key's flag tuple is fine inside the trace
    res = run_snippet(tmp_path, KEY001_FLAGGED.replace(
        "log_level", "use_pallas"))
    assert codes(res) == []


def test_key001_discriminant_flag_clean(tmp_path):
    # serving_kv_dtype rides the key as a ("kv", dtype) component
    res = run_snippet(tmp_path, KEY001_FLAGGED.replace(
        "log_level", "serving_kv_dtype"))
    assert codes(res) == []


def test_key001_builder_through_partial(tmp_path):
    res = run_snippet(tmp_path, "    import functools\n"
                      + KEY001_FLAGGED.replace(
                          "decode_program_cache().get(key, _build)",
                          "decode_program_cache().get(key,\n"
                          "            functools.partial(_build))"))
    assert codes(res) == ["KEY001"]


def test_key001_unreachable_read_clean(tmp_path):
    # the same read in a function NOT reachable from any builder is
    # eager code — not this rule's business
    res = run_snippet(tmp_path, """
        from .flags import get_flag


        def eager_log(x):
            return x * get_flag("log_level")
    """)
    assert codes(res) == []


KEY001_SNAP = """
    from .program_cache import decode_program_cache
    from . import flags


    def _build(note_trace):
        snap = flags.snapshot()

        def step(x):
            return x * snap.log_level
        return step


    def admit(key):
        return decode_program_cache().get(key, _build)
"""


def test_key001_snapshot_attribute_read(tmp_path):
    res = run_snippet(tmp_path, KEY001_SNAP)
    assert codes(res) == ["KEY001"]
    assert "snap.log_level" in res.findings[0].message


def test_key001_snapshot_program_flag_clean(tmp_path):
    res = run_snippet(tmp_path, KEY001_SNAP.replace(
        "snap.log_level", "snap.use_pallas"))
    assert codes(res) == []


def test_key001_pragma(tmp_path):
    res = run_snippet(tmp_path, KEY001_FLAGGED.replace(
        'return x * get_flag("log_level")',
        'return x * get_flag("log_level")'
        '  # keycheck: disable=KEY001'))
    assert codes(res) == []
    assert len(res.suppressed) == 1


# ---------------------------------------------------------------- KEY002
KEY002_FLAGGED = """
    import functools

    from .program_cache import decode_program_cache


    def _build(note_trace, table=None):
        return table


    class Engine:
        def admit(self, key):
            builder = functools.partial(_build, table=self._table)
            return decode_program_cache().get(key, builder)
"""


def test_key002_partial_binds_mutable_state(tmp_path):
    res = run_snippet(tmp_path, KEY002_FLAGGED)
    assert codes(res) == ["KEY002"]
    assert "table=self._table" in res.findings[0].message


def test_key002_key_derived_state_clean(tmp_path):
    # tp_degree is derivable from the key (the ("tp", N) component)
    res = run_snippet(tmp_path, KEY002_FLAGGED.replace(
        "self._table", "self.tp_degree"))
    assert codes(res) == []


def test_key002_snapshot_state_clean(tmp_path):
    # the flag snapshot IS a key component (the flags tuple)
    res = run_snippet(tmp_path, KEY002_FLAGGED.replace(
        "self._table", "self._flags"))
    assert codes(res) == []


def test_key002_local_closure_builder(tmp_path):
    res = run_snippet(tmp_path, """
        from .program_cache import decode_program_cache


        class Engine:
            def admit(self, key):
                def builder(note_trace):
                    return self._table
                return decode_program_cache().get(key, builder)
    """)
    assert codes(res) == ["KEY002"]
    assert "closes over self._table" in res.findings[0].message


def test_key002_pragma(tmp_path):
    res = run_snippet(tmp_path, KEY002_FLAGGED.replace(
        "builder = functools.partial(_build, table=self._table)",
        "builder = functools.partial(_build, table=self._table)"
        "  # keycheck: disable=KEY002"))
    assert codes(res) == []
    assert len(res.suppressed) == 1


# ---------------------------------------------------------------- KEY003
KEY003_FLAGGED = """
    from .program_cache import DecodeKey


    def mint(sig):
        return DecodeKey(kind="decode_generic", model_sig=sig,
                         batch_bucket=4, page_budget=(1, 8, 4),
                         dtype="float32", flags=(),
                         extra=({"mode": 1},))
"""


def test_key003_dict_in_extra(tmp_path):
    res = run_snippet(tmp_path, KEY003_FLAGGED)
    assert codes(res) == ["KEY003"]
    assert "unhashable dict" in res.findings[0].message


def test_key003_float_in_extra(tmp_path):
    res = run_snippet(tmp_path, KEY003_FLAGGED.replace(
        'extra=({"mode": 1},)', "extra=(0.5,)"))
    assert codes(res) == ["KEY003"]
    assert "float" in res.findings[0].message


def test_key003_device_value_in_field(tmp_path):
    res = run_snippet(tmp_path, ("    import jax.numpy as jnp\n"
                                 + KEY003_FLAGGED).replace(
        "batch_bucket=4", "batch_bucket=jnp.argmax(sig)"))
    assert any(c == "KEY003" for c in codes(res))
    assert any("device" in f.message for f in res.findings)


def test_key003_host_tuple_clean(tmp_path):
    res = run_snippet(tmp_path, KEY003_FLAGGED.replace(
        'extra=({"mode": 1},)', 'extra=(("kv", "int8"),)'))
    assert codes(res) == []


def test_key003_pragma(tmp_path):
    res = run_snippet(tmp_path, KEY003_FLAGGED.replace(
        'extra=({"mode": 1},))',
        'extra=({"mode": 1},))  # keycheck: disable=KEY003'))
    assert codes(res) == []
    assert len(res.suppressed) == 1


# ---------------------------------------------------------------- KEY004
KEY004_FLAGGED = """
    from .program_cache import DecodeKey


    class Engine:
        def mint(self):
            return DecodeKey(kind="prefill", model_sig="m",
                             batch_bucket=len(self._queue),
                             page_budget=(1, 8, 4), dtype="f32",
                             flags=())
"""


def test_key004_live_container_length(tmp_path):
    res = run_snippet(tmp_path, KEY004_FLAGGED)
    assert codes(res) == ["KEY004"]
    assert "len(self._queue)" in res.findings[0].message


def test_key004_step_attribute(tmp_path):
    res = run_snippet(tmp_path, KEY004_FLAGGED.replace(
        "len(self._queue)", "self._step"))
    assert codes(res) == ["KEY004"]
    assert "step-like" in res.findings[0].message


def test_key004_clock_read(tmp_path):
    res = run_snippet(tmp_path, ("    import time\n"
                                 + KEY004_FLAGGED).replace(
        "len(self._queue)", "int(time.perf_counter())"))
    assert codes(res) == ["KEY004"]
    assert "clock" in res.findings[0].message


def test_key004_bucketed_value_clean(tmp_path):
    # the bucket (engine geometry) is the RIGHT thing to key
    res = run_snippet(tmp_path, KEY004_FLAGGED.replace(
        "len(self._queue)", "self.max_batch"))
    assert codes(res) == []


def test_key004_pragma(tmp_path):
    res = run_snippet(tmp_path, KEY004_FLAGGED.replace(
        "batch_bucket=len(self._queue),",
        "batch_bucket=len(self._queue),"
        "  # keycheck: disable=KEY004"))
    assert codes(res) == []
    assert len(res.suppressed) == 1


# ---------------------------------------------------------------- KEY005
KEY005_FLAGGED = """
    from . import flags


    def arm_checker():
        flags.set_flags({"check_nan_inf": True})
"""


def test_key005_program_flag_set_without_rearm(tmp_path):
    res = run_snippet(tmp_path, KEY005_FLAGGED)
    assert codes(res) == ["KEY005"]
    assert "check_nan_inf" in res.findings[0].message


def test_key005_rearm_clean(tmp_path):
    res = run_snippet(tmp_path, KEY005_FLAGGED.replace(
        'flags.set_flags({"check_nan_inf": True})',
        'flags.set_flags({"check_nan_inf": True})\n'
        '        clear_decode_program_cache()').replace(
        "from . import flags",
        "from . import flags\n"
        "    from .program_cache import clear_decode_program_cache"))
    assert codes(res) == []


def test_key005_minting_a_new_key_clean(tmp_path):
    # re-keying is the other legitimate discipline: the new key's flag
    # tuple separates the programs
    res = run_snippet(tmp_path, KEY005_FLAGGED.replace(
        "from . import flags",
        "from . import flags\n"
        "    from .program_cache import DecodeKey").replace(
        'flags.set_flags({"check_nan_inf": True})',
        'flags.set_flags({"check_nan_inf": True})\n'
        '        return DecodeKey(kind="prefill", model_sig="m",\n'
        '                         batch_bucket=1, page_budget=(),\n'
        '                         dtype="f", flags=())'))
    assert codes(res) == []


def test_key005_eager_flag_clean(tmp_path):
    # benchmark is an eager flag — flipping it invalidates nothing
    res = run_snippet(tmp_path, KEY005_FLAGGED.replace(
        '"check_nan_inf": True', '"benchmark": True'))
    assert codes(res) == []


def test_key005_fixture_declares_own_program_flags(tmp_path):
    # the vocabulary is read from the ANALYZED package's flags.py, not
    # hardcoded: a fixture declaring its own PROGRAM_FLAGS retargets
    # the rule (and un-tracks the real package's names)
    res = run_snippet(tmp_path, """
        from . import flags


        def toggle():
            flags.set_flags({"my_knob": 1})


        def toggle_other():
            flags.set_flags({"check_nan_inf": True})
    """, extra={"flags.py": 'PROGRAM_FLAGS = ("my_knob",)\n'})
    assert codes(res) == ["KEY005"]
    assert "my_knob" in res.findings[0].message


def test_key005_pragma(tmp_path):
    res = run_snippet(tmp_path, KEY005_FLAGGED.replace(
        'flags.set_flags({"check_nan_inf": True})',
        'flags.set_flags({"check_nan_inf": True})'
        '  # keycheck: disable=KEY005'))
    assert codes(res) == []
    assert len(res.suppressed) == 1


# ---------------------------------------------------------------- KEY006
def test_key006_unregistered_tag(tmp_path):
    res = run_snippet(tmp_path, KEY003_FLAGGED.replace(
        'extra=({"mode": 1},)', 'extra=(("zzz", 1),)'))
    assert codes(res) == ["KEY006"]
    assert "'zzz'" in res.findings[0].message
    assert "key_vocab" in res.findings[0].message


def test_key006_fixture_declares_own_vocabulary(tmp_path):
    # same retargeting as KEY005: a fixture package's own
    # analysis/key_vocab.py registers the tag, silencing the rule
    res = run_snippet(tmp_path, KEY003_FLAGGED.replace(
        'extra=({"mode": 1},)', 'extra=(("zzz", 1),)'),
        extra={"analysis/key_vocab.py":
               'EXTRA_TAGS = frozenset({"zzz"})\n'
               'EXTRA_ATOMS = frozenset()\n'})
    assert codes(res) == []


KEY006_CONFLICT = """
    from .program_cache import DecodeKey


    def mint_a(sig):
        return DecodeKey(kind="decode_fused", model_sig=sig,
                         batch_bucket=4, page_budget=(1, 8, 4),
                         dtype="f32", flags=(), extra=(8,))


    def mint_b(sig):
        return DecodeKey(kind="decode_fused", model_sig=sig,
                         batch_bucket=4, page_budget=(1, 8, 4),
                         dtype="f32", flags=(),
                         extra=(("kv", "int8"),))
"""


def test_key006_schema_conflict(tmp_path):
    res = run_snippet(tmp_path, KEY006_CONFLICT)
    assert codes(res) == ["KEY006"]
    assert "one kind = one extra schema" in res.findings[0].message
    assert "decode_fused" in res.findings[0].message


def test_key006_same_schema_twice_clean(tmp_path):
    res = run_snippet(tmp_path, KEY006_CONFLICT.replace(
        "extra=(8,)", 'extra=(("kv", "native"),)'))
    assert codes(res) == []


def test_key006_minter_appended_tag(tmp_path):
    # ServingEngine._key-style minter: grammar appended to the extra
    # parameter in the body is vocabulary-checked too
    res = run_snippet(tmp_path, """
        from .program_cache import DecodeKey


        class Engine:
            def _key(self, kind, extra=()):
                extra = tuple(extra) + (("zzz", self.z),)
                return DecodeKey(kind=kind, model_sig="m",
                                 batch_bucket=1, page_budget=(),
                                 dtype="f", flags=(), extra=extra)

            def decode(self):
                return self._key("decode_fused")
    """)
    assert codes(res) == ["KEY006"]
    assert "appended by minter" in res.findings[0].message


def test_key006_minter_census(tmp_path):
    res = run_snippet(tmp_path, """
        from .program_cache import DecodeKey


        class Engine:
            def _key(self, kind, extra=()):
                extra = tuple(extra) + (("kv", self.kv_dtype),)
                return DecodeKey(kind=kind, model_sig="m",
                                 batch_bucket=1, page_budget=(),
                                 dtype="f", flags=(), extra=extra)

            def decode(self):
                return self._key("decode_fused")

            def prefill(self):
                return self._key("prefill")
    """)
    assert codes(res) == []
    assert res.n_minters == 1
    assert res.census["minters"] == ["Engine._key"]
    assert res.census["kinds"] == ["decode_fused", "prefill"]
    assert res.census["extra_tags"] == ["kv"]
    assert any("via=Engine._key" in s
               for s in res.census["decode_key_sites"])


def test_key006_pragma(tmp_path):
    res = run_snippet(tmp_path, KEY003_FLAGGED.replace(
        'extra=({"mode": 1},))',
        'extra=(("zzz", 1),))  # keycheck: disable=KEY006'))
    assert codes(res) == []
    assert len(res.suppressed) == 1


# ---------------------------------------------------- machinery / parse
def test_rule_catalogue_complete():
    assert set(KEY_RULES) == {"KEY001", "KEY002", "KEY003", "KEY004",
                              "KEY005", "KEY006"}
    assert set(AnalyzerConfig().rules) == set(KEY_RULES)


def test_vocabulary_no_drift():
    """Satellite no-drift contract: the vocabulary keycheck derives by
    AST from the real package equals the key_vocab constants that
    generation/serving.py imports at runtime — and KEY003's device
    detector IS statecheck's (same object, the faultcheck precedent)."""
    assert kr.device_producing is bv.device_producing

    parsed = tc.parse_package(PKG)
    assert km.program_flags_vocabulary(parsed.modules) == \
        key_vocab.PROGRAM_FLAGS_FALLBACK
    vocab = km.extra_vocabulary(parsed.modules)
    assert vocab.tags == key_vocab.EXTRA_TAGS
    assert vocab.atoms == key_vocab.EXTRA_ATOMS
    assert vocab.discriminants == frozenset(key_vocab.DISCRIMINANT_FLAGS)
    assert vocab.source.endswith("analysis/key_vocab.py")
    # every discriminant (and every PROGRAM_FLAGS member) is a real,
    # declared flag — a typo'd vocabulary entry would silently track
    # nothing
    flag_names = km.declared_flag_names(parsed.modules)
    assert flag_names is not None
    assert key_vocab.PROGRAM_FLAGS_FALLBACK <= flag_names
    assert frozenset(key_vocab.DISCRIMINANT_FLAGS) <= flag_names


# one module that trips all SIX suites at once: TRC001 (flag read under
# trace), MSH001 (unbound collective axis), FLT004 (unbounded retry
# loop), KRN001 (off-grid BlockSpec), STC001 (device value in an
# exported dict bundle), KEY003 (dict literal in a DecodeKey extra)
SEXT_SOURCE = """
    import time
    import jax
    from jax import lax
    from jax.experimental import pallas as pl
    from .flags import get_flag
    from .program_cache import DecodeKey

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

    def harvest_request(x):
        return {"v": 1, "last": lax.exp(x)}

    def decode_key(sig):
        return DecodeKey(kind="decode_generic", model_sig=sig,
                         batch_bucket=4, page_budget=(1, 8, 4),
                         dtype="float32", flags=(),
                         extra=({"mode": 1},))
"""

_SEXT_LINES = {
    "tracecheck": ('return x * get_flag("use_pallas")', "TRC001"),
    "meshcheck": ('return lax.psum(x, "tp")', "MSH001"),
    "faultcheck": ("time.sleep(0.1)", "FLT004"),
    "kernelcheck": ("in_specs=[pl.BlockSpec((8, 96), lambda i: (i, 0))],",
                    "KRN001"),
    "statecheck": ('return {"v": 1, "last": lax.exp(x)}', "STC001"),
    "keycheck": ('extra=({"mode": 1},))', "KEY003"),
}


def _sext_results(tmp_path, source):
    pkg = tmp_path / "fixpkg"
    pkg.mkdir(exist_ok=True)
    (pkg / "__init__.py").write_text("")
    (pkg / "mod.py").write_text(textwrap.dedent(source))
    return {
        "tracecheck": tc.analyze_package(str(pkg)),
        "meshcheck": mc.analyze_package(str(pkg)),
        "faultcheck": fc.analyze_package(str(pkg)),
        "kernelcheck": kn.analyze_package(str(pkg)),
        "statecheck": sc.analyze_package(str(pkg)),
        "keycheck": analyze_package(str(pkg)),
    }


def test_six_suite_pragma_isolation_matrix(tmp_path):
    """Every suite's pragma silences ONLY its own rule: a 6x6 matrix
    over one module that trips TRC001 + MSH001 + FLT004 + KRN001 +
    STC001 + KEY003."""
    base = {s: [f.rule for f in r.findings]
            for s, r in _sext_results(tmp_path, SEXT_SOURCE).items()}
    assert base == {"tracecheck": ["TRC001"], "meshcheck": ["MSH001"],
                    "faultcheck": ["FLT004"], "kernelcheck": ["KRN001"],
                    "statecheck": ["STC001"], "keycheck": ["KEY003"]}

    for pragma_tool in _SEXT_LINES:
        src = SEXT_SOURCE
        for target_suite, (line, rule) in _SEXT_LINES.items():
            src = src.replace(
                line, f"{line}  # {pragma_tool}: disable={rule}")
        results = _sext_results(tmp_path, src)
        for suite, (_, rule) in _SEXT_LINES.items():
            found = [f.rule for f in results[suite].findings]
            if suite == pragma_tool:
                assert found == [], (pragma_tool, suite, found)
                assert len(results[suite].suppressed) == 1
            else:
                # the foreign pragma (even naming this suite's rule
                # code) must not silence this suite
                assert found == [rule], (pragma_tool, suite, found)


def test_foreign_pragma_with_own_code_does_not_silence(tmp_path):
    # a statecheck pragma spelling a KEY code still never crosses
    # suites — pragma scope is the tool name, not the rule code
    res = run_snippet(tmp_path, KEY003_FLAGGED.replace(
        'extra=({"mode": 1},))',
        'extra=({"mode": 1},))  # statecheck: disable=KEY003'))
    assert codes(res) == ["KEY003"]


def test_baseline_round_trip_stable(tmp_path):
    pkg = tmp_path / "fixpkg"
    pkg.mkdir()
    (pkg / "__init__.py").write_text("")
    (pkg / "mod.py").write_text(textwrap.dedent(KEY003_FLAGGED))
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
        "X = 1\nY = 2\n\n" + textwrap.dedent(KEY003_FLAGGED))
    new, leftovers = subtract_baseline(
        analyze_package(str(pkg)).findings, load_baseline(str(b1)))
    assert new == [] and not leftovers


def test_baseline_multiset_semantics(tmp_path):
    # two textually identical dict-in-extra mints in one function: one
    # baselined entry forgives exactly one of them
    src = """
        from .program_cache import DecodeKey


        def mint(sig):
            a = DecodeKey(kind="decode_generic", model_sig=sig,
                          batch_bucket=4, page_budget=(1, 8, 4),
                          dtype="float32", flags=(),
                          extra=({"mode": 1},))
            a = DecodeKey(kind="decode_generic", model_sig=sig,
                          batch_bucket=4, page_budget=(1, 8, 4),
                          dtype="float32", flags=(),
                          extra=({"mode": 1},))
            return a
    """
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
    """All SIX suites over ONE parse must report exactly what they
    report standalone, with keycheck running first AND last — its
    context build is a pure read of the shared ModuleInfos."""
    kc_alone = analyze_package(PKG)
    tc_alone = tc.analyze_package(PKG)
    sc_alone = sc.analyze_package(PKG)

    parsed = tc.parse_package(PKG)
    kc_first = analyze_package(PKG, parsed=parsed)
    tc_mid = tc.analyze_package(PKG, parsed=parsed)
    mc_mid = mc.analyze_package(PKG, parsed=parsed)
    fc_mid = fc.analyze_package(PKG, parsed=parsed)
    kn_mid = kn.analyze_package(PKG, parsed=parsed)
    sc_last = sc.analyze_package(PKG, parsed=parsed)

    parsed2 = tc.parse_package(PKG)
    tc_first = tc.analyze_package(PKG, parsed=parsed2)
    mc_mid2 = mc.analyze_package(PKG, parsed=parsed2)
    fc_mid2 = fc.analyze_package(PKG, parsed=parsed2)
    kn_mid2 = kn.analyze_package(PKG, parsed=parsed2)
    sc_mid = sc.analyze_package(PKG, parsed=parsed2)
    kc_last = analyze_package(PKG, parsed=parsed2)

    def sig(res):
        return [f.format() for f in res.findings]

    assert sig(kc_first) == sig(kc_alone) == sig(kc_last)
    assert sig(tc_mid) == sig(tc_alone) == sig(tc_first)
    assert sig(sc_last) == sig(sc_alone) == sig(sc_mid)
    assert sig(mc_mid) == sig(mc_mid2)
    assert sig(fc_mid) == sig(fc_mid2)
    assert sig(kn_mid) == sig(kn_mid2)
    # the key census must be order-independent too
    for a in (kc_first, kc_last):
        assert (a.n_key_sites, a.n_kinds, a.n_tags, a.n_builders,
                a.n_admissions, a.n_minters) == \
            (kc_alone.n_key_sites, kc_alone.n_kinds, kc_alone.n_tags,
             kc_alone.n_builders, kc_alone.n_admissions,
             kc_alone.n_minters)
        assert a.census == kc_alone.census


def test_exclude_patterns_apply_to_shared_parse(tmp_path):
    pkg = tmp_path / "fixpkg"
    pkg.mkdir()
    (pkg / "__init__.py").write_text("")
    (pkg / "mod.py").write_text(textwrap.dedent(KEY003_FLAGGED))
    parsed = tc.parse_package(str(pkg))
    cfg = AnalyzerConfig(exclude_patterns=("mod.py",))
    assert analyze_package(str(pkg), cfg, parsed=parsed).findings == []
    assert analyze_package(str(pkg), cfg).findings == []


# ------------------------------------------------------------------- CLI
def test_single_suite_cli_exit_codes(tmp_path, capsys):
    from paddle_tpu.analysis.keycheck import cli

    pkg = tmp_path / "fixpkg"
    pkg.mkdir()
    (pkg / "__init__.py").write_text("")
    (pkg / "mod.py").write_text(textwrap.dedent(KEY003_FLAGGED))

    # a rule-filtered run must never write the baseline (it would
    # clobber the other rules' entries)
    rc = cli.main([str(pkg), "--rules", "KEY003", "--update-baseline"])
    assert rc == 2
    assert "clobber" in capsys.readouterr().err

    rc = cli.main([str(pkg), "--no-baseline"])
    assert rc == 1
    assert "KEY003" in capsys.readouterr().out

    # the --json payload carries the key census alongside findings
    rc = cli.main([str(pkg), "--no-baseline", "--json"])
    assert rc == 1
    payload = json.loads(capsys.readouterr().out)
    assert [f["rule"] for f in payload["findings"]] == ["KEY003"]
    assert payload["key_sites"] == 1
    assert payload["census"]["kinds"] == ["decode_generic"]
    assert payload["census"]["vocab_source"] == ""   # fixture: fallback

    rc = cli.main([str(pkg), "--rules", "KEY001", "--no-baseline"])
    assert rc == 0          # KEY003 not selected
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
    assert "KEY006" in capsys.readouterr().out

    rc = cli.main([str(tmp_path / "nope")])
    assert rc == 2
    capsys.readouterr()


def test_standalone_tools_loader(tmp_path):
    # tools/keycheck.py must run as a plain script (no package install,
    # no jax import) and exit 1 on a finding, with the census in --json
    pkg = tmp_path / "fixpkg"
    pkg.mkdir()
    (pkg / "__init__.py").write_text("")
    (pkg / "mod.py").write_text(textwrap.dedent(KEY003_FLAGGED))
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "keycheck.py"),
         str(pkg), "--no-baseline", "--json"],
        capture_output=True, text=True)
    assert r.returncode == 1, r.stdout + r.stderr
    payload = json.loads(r.stdout)
    assert [f["rule"] for f in payload["findings"]] == ["KEY003"]
    for k in ("decode_key_sites", "kinds", "extra_tags", "extra_atoms",
              "builders", "snapshot_sites"):
        assert k in payload["census"], k


def test_unified_cli_runs_keycheck_as_sixth_suite(tmp_path):
    pkg = tmp_path / "fixpkg"
    pkg.mkdir()
    (pkg / "__init__.py").write_text("")
    (pkg / "mod.py").write_text(textwrap.dedent(SEXT_SOURCE))
    (tmp_path / "tools").mkdir()
    env = dict(os.environ, PYTHONPATH=REPO)
    cli = [sys.executable, os.path.join(REPO, "tools", "analyze.py")]

    r = subprocess.run(cli + [str(pkg), "--no-baseline", "--json"],
                       capture_output=True, text=True, env=env)
    assert r.returncode == 1, r.stdout + r.stderr
    payload = json.loads(r.stdout)
    want = {"tracecheck": "TRC001", "meshcheck": "MSH001",
            "faultcheck": "FLT004", "kernelcheck": "KRN001",
            "statecheck": "STC001", "keycheck": "KEY003"}
    for suite, rule in want.items():
        assert [f["rule"] for f in payload[suite]["findings"]] == [rule]

    # --suite keycheck runs ONLY the KEY rules
    r = subprocess.run(cli + [str(pkg), "--suite", "keycheck",
                              "--no-baseline"],
                       capture_output=True, text=True, env=env)
    assert r.returncode == 1
    assert "KEY003" in r.stdout
    assert all(c not in r.stdout for c in ("TRC001", "MSH001", "FLT004",
                                           "KRN001", "STC001"))

    # --update-baseline writes all six, then the gate is clean
    r = subprocess.run(cli + [str(pkg), "--update-baseline"],
                       capture_output=True, text=True, env=env)
    assert r.returncode == 0, r.stdout + r.stderr
    for suite in ("tracecheck", "meshcheck", "faultcheck", "kernelcheck",
                  "statecheck", "keycheck"):
        assert (tmp_path / "tools" / f"{suite}_baseline.json").exists()
    r = subprocess.run(cli + [str(pkg)], capture_output=True, text=True,
                       env=env)
    assert r.returncode == 0, r.stdout + r.stderr


# ------------------------------------------------------- the tier-1 gate
def test_package_gate_zero_new_findings():
    """THE gate: the whole package against the checked-in baseline —
    which is EMPTY by construction (the real findings this suite
    surfaced were FIXED in this round: the missing cache re-arms in
    amp/debugging.py and utils/install_check.py, and the decode_fused
    double extra schema at the tp all-singleton arm; the six
    model-object closures are the documented pragma'd exemplar); any
    new finding fails tier-1."""
    t0 = time.time()
    result = analyze_package(PKG)
    elapsed = time.time() - t0
    assert not result.errors, result.errors

    baseline = load_baseline(BASELINE)
    assert not baseline, "keycheck's baseline must stay EMPTY"
    new, leftovers = subtract_baseline(result.findings, baseline)
    assert new == [], (
        "keycheck found NEW program-identity findings:\n"
        + "\n".join(f.format() for f in new)
        + "\n\nfix them or add a '# keycheck: disable=KEY00x' pragma "
          "with a reason — do NOT baseline key-soundness findings")
    assert not leftovers
    assert elapsed < 15.0, f"keycheck took {elapsed:.1f}s"


def test_six_suite_gate_wall_clock():
    """The combined tier-1 lint gate (ONE parse, six analyzers) stays
    inside the ~15 s budget.  This times the heaviest single
    measurement in the lint tests, so a loaded box gets ONE retry: a
    contention transient cannot breach the budget twice, a real
    slowdown breaches it every time."""
    for attempt in (1, 2):
        t0 = time.time()
        parsed = tc.parse_package(PKG)
        assert not parsed.errors, parsed.errors
        for mod in (tc, mc, fc, kn, sc):
            assert not mod.analyze_package(PKG, parsed=parsed).errors
        assert not analyze_package(PKG, parsed=parsed).errors
        elapsed = time.time() - t0
        if elapsed < 15.0:
            return
    raise AssertionError(
        f"six-suite gate took {elapsed:.1f}s on both attempts")


def test_package_gate_scale_sanity():
    """Coverage floor: if the key census silently collapses the gate
    would pass vacuously.  Lower bounds, not exact counts."""
    result = analyze_package(PKG)
    assert result.n_files > 150
    assert result.n_functions > 2000
    assert result.n_key_sites >= 8
    assert result.n_kinds >= 5
    assert result.n_tags >= 4
    assert result.n_builders >= 6
    assert result.n_admissions >= 6
    assert result.n_minters >= 2          # _key, _spec_program
    census = result.census
    assert {"decode_fused", "decode_fused_nlayer", "decode_generic",
            "prefill", "prefill_chunk", "spec_draft",
            "spec_verify"} <= set(census["kinds"])
    assert {"kv", "wt", "tp", "nlayer"} <= set(census["extra_tags"])
    assert "ServingEngine._key" in census["minters"]
    assert census["program_flags"] == \
        sorted(key_vocab.PROGRAM_FLAGS_FALLBACK)
    assert len(census["program_flags"]) == 11
    assert census["vocab_source"].endswith("analysis/key_vocab.py")
