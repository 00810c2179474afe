"""Program-identity vocabulary: the ``DecodeKey.extra`` tag grammar.

jax-free on purpose (same contract as :mod:`.tile_geometry` and
:mod:`.statecheck.bundle_vocab`): this module is the ONE place the
serving stack and the keycheck lint agree on what may appear inside a
program-cache key's ``extra`` tuple.  ``generation/serving.py`` imports
these constants back when it mints keys, and keycheck's KEY006 reads
this file (by AST, at analysis time) to decide which tags are
registered — identical-by-object, so the lint and the runtime can never
drift (the tile_geometry/bundle_vocab coupling pattern; no-drift tested
from both sides).

Grammar recap (see generation/program_cache.py):

- ``extra`` is a flat tuple.  Kind-specific geometry comes FIRST
  (chunk lengths, spec-γ rungs, the ``("nlayer", (sizes...))`` tag +
  layer-group shape), then the engine-appended discriminant pairs
  ``("kv", dtype)``, ``("wt", dtype)`` and — only under tensor
  parallelism — ``("tp", N)``.
- A *tag* is the string head of a ``(tag, value)`` pair.
- An *atom* is a bare string marker (the spec-decode path/mode
  markers: ``"fused"``/``"generic"``, ``"sample"``/``"greedy"``).

New key families (tree-spec ``(rung, tree)`` programs, LoRA adapter
stacks, long-context ladders) must register their tags/atoms here —
KEY006 flags any string that appears in an ``extra`` tuple without a
registration, which is what turns "two teams invented colliding
positional tuples" into a lint error instead of a cache collision.
"""

from __future__ import annotations

# ----------------------------------------------------------- extra tags
# heads of (tag, value) pairs inside DecodeKey.extra
TAG_KV = "kv"            # ("kv", dtype)   — paged-KV element dtype
TAG_WT = "wt"            # ("wt", dtype)   — fused-decode weight-tile dtype
TAG_TP = "tp"            # ("tp", N)       — tensor-parallel degree (N > 1)
TAG_NLAYER = "nlayer"    # ("nlayer", (sizes...)) — fused layer-group shape

EXTRA_TAGS = frozenset({TAG_KV, TAG_WT, TAG_TP, TAG_NLAYER})

# ---------------------------------------------------------- extra atoms
# bare string markers (spec-decode draft program path/mode)
ATOM_FUSED = "fused"     # draft runs the fused single-block path
ATOM_GENERIC = "generic"  # draft runs the generic GSPMD path
ATOM_SAMPLE = "sample"   # draft samples (paired with top-k in the tuple)
ATOM_GREEDY = "greedy"   # draft decodes greedily

EXTRA_ATOMS = frozenset({ATOM_FUSED, ATOM_GENERIC, ATOM_SAMPLE,
                         ATOM_GREEDY})

# ------------------------------------------------- program-flag universe
# Fallback copy of flags.PROGRAM_FLAGS for analysis runs where the
# analyzed package has no flags.py (fixtures).  Against the real
# package keycheck reads flags.py's PROGRAM_FLAGS tuple by AST (the
# meshcheck _HYBRID_AXES idiom) and this set is only a safety net —
# tests/test_keycheck.py asserts the two never drift.
PROGRAM_FLAGS_FALLBACK = frozenset({
    "fused_block_decode", "fused_block_layers", "use_pallas",
    "flash_attn_min_seqlen", "flash_compact_stats", "flash_dispatch_table",
    "tpu_matmul_precision", "embedding_matmul_grad", "deterministic",
    "check_nan_inf", "check_nan_inf_level",
})

# Flags that are eager-only BY DESIGN because their value rides the key
# as a component instead of the flag tuple (the serving_kv_dtype
# annotated-exemplar shape): a traced read of one of these would be a
# KEY001 finding, but their names appearing in builder closures or
# flag reads OUTSIDE traced bodies is fine — the key discriminates.
DISCRIMINANT_FLAGS = {
    "serving_kv_dtype": TAG_KV,              # rides ("kv", dtype)
    "fused_weight_dtype": TAG_WT,            # rides ("wt", dtype)
    "serving_tp_degree": TAG_TP,             # rides ("tp", N)
    "serving_prefill_chunk": "extra[0]",     # chunk length in extra
    "serving_spec_sync_chunk": "extra[0]",   # sync-chunk length in extra
    "serving_spec_gamma": "extra[0]",        # spec rung γ in extra
}

# Engine attributes a builder MAY close over without a KEY002 finding:
# each is derivable from a key component (so two engines sharing a key
# hold equal values) or pins process-global topology the key's ("tp",N)
# pair already discriminates.
KEY_DERIVED_ATTRS = frozenset({
    "kv_dtype",          # rides ("kv", dtype)
    "weight_dtype",      # rides ("wt", dtype)
    "tp_degree",         # rides ("tp", N)
    "chunk",             # rides extra[0] of prefill_chunk keys
    "spec_sync_chunk",   # rides extra[0] of spec sync-chunk keys
    "max_batch",         # rides batch_bucket
    "_tp_mesh",          # process device set, pinned by ("tp", N)
    "_tp_axis",          # constant axis name over _tp_mesh
})

# Engine attributes that HOLD the program-flag snapshot: closing over
# one of these is the sanctioned way to thread flags into a traced
# body (the snapshot's as_tuple() is the key's flags component).
SNAPSHOT_ATTRS = frozenset({"_flags"})
