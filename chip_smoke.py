#!/usr/bin/env python
"""The quickest proof that the system still starts on the chip.

``python chip_smoke.py`` drives the two main paths once, through the entry
points a user calls, on ONE TPU chip, in this one process:

  device        paddle.run_check(); where tensors land; the peak table
  train         GPT-3 345M at full published size through bench.py's
                recipe + hapi.TrainStep: 5 steps on a repeated batch
  serve         the same weights through ServingEngine (paged + chunked
                prefill + decode) against its XLA-twin engine and
                model.generate()
  fused_decode  a Llama-layout model at Llama-2-7B widths, 4 layers deep,
                through the fused block-decode engine against the same
                engine with FLAGS_fused_block_decode off
  hybrid        granite-4.0-h-micro whole (36 Mamba-2 + 4 attention
                layers, the benchmark's configuration and weights) through
                ServingEngine: one prompt through a full and a padded
                prefill chunk, then decoded, against the benchmark's plain
                reference
  blocks        sdar-30b-a3b-chat (6 layers of 128 experts, the
                benchmark's configuration and weights) through
                ServingEngine: one prompt whose whole blocks pass a full
                and a padded block-causal chunk and whose remainder starts
                the first block, three blocks generated, every reveal held
                against the plain reference
  window        trinity-mini (5 layers: one dense, three window layers
                and a global one among the sparse four; the benchmark's
                configuration and weights) through ServingEngine: one
                prompt longer than window + chunk through its chunks,
                the window layers' pages given back between them and
                taken again, then decode, every token held against the
                plain reference
  moe_train     a small Mellum 2 (three window layers and a full one,
                heads of 128, a router over 16 experts of which 8 are
                held) through hapi.TrainStep: steps on a repeated batch,
                the windowed flash kernels and gmm / tgmm in the step

``python chip_smoke.py --chips 4`` runs ONLY the Fleet hybrid path
(dp2 x mp2 TrainStep at Llama-2-7B widths, 2 layers) and its one-chip twin.

It refuses any platform but ``tpu``, every failed check is an exception
(no phase is caught into a key), each phase prints one JSON line of facts
— they are facts about the run, not measurements — and the last line is
``{"ok": true, "device": {"platform", "kind", "count"}}`` as JAX reports
the device. ``run_phases`` is the same code at caller-given sizes:
tests/test_chip_smoke.py drives it tiny on the CPU.
"""

import argparse
import collections
import dataclasses
import gc
import json
import sys
import time
from typing import Optional, Tuple

import numpy as np

from bench import build_train_setup, use_compile_cache

# bf16 tolerance as tests/test_paged_attention.py::test_bf16_pool documents
# it: kernels that agree to 2e-5 in f32 agree to this in bf16
BF16_RTOL = BF16_ATOL = 3e-2


@dataclasses.dataclass(frozen=True)
class Sizes:
    """What the phases run at. ``on_chip`` arms the checks only a TPU can
    pass (Pallas custom calls in the programs, TPUPlace, the peak table)."""
    on_chip: bool
    train_model: str                    # a bench.build_train_setup name
    train_shape: Tuple[int, int]        # (batch, seq) the recipe must give
    train_steps: int
    serve_batch: int
    page_size: int
    max_seq_len: int
    prompt_lens: Tuple[int, ...]        # served AND generate()d; same
    new_tokens: int                     # lengths batch into one generate
    llama: dict                         # LlamaConfig widths (both phases)
    fused_layers: int
    fused_prompt_lens: Tuple[int, ...]
    fused_new_tokens: int
    hybrid_layers: int
    hybrid_shape: Tuple[int, int]
    hybrid_steps: int
    granite_prompt_len: int = 416           # a full chunk, then a padded one
    granite_new_tokens: int = 8
    # a configuration in the benchmark's form; None: the benchmark's own
    # file, benchmark/configs/granite-4.0-h-micro.json
    granite: Optional[dict] = None
    prefill_chunk: Optional[int] = None     # None: FLAGS_serving_prefill_chunk
    # 416 whole-block tokens (a full chunk, then a padded one) + 3 that
    # start the first block; 1 + 4 + 4 new tokens
    sdar_prompt_len: int = 419
    sdar_new_tokens: int = 9
    # None: benchmark/configs/sdar-30b-a3b-chat.json
    sdar: Optional[dict] = None
    # four chunks, past window + chunk: pages given back are taken again
    trinity_prompt_len: int = 3840
    trinity_new_tokens: int = 8
    # None: benchmark/configs/trinity-mini.json
    trinity: Optional[dict] = None
    # MellumConfig widths of the moe_train phase, and its (batch, seq)
    mellum: Optional[dict] = None
    # 4,096: FLAGS_flash_dispatch_table sends 2,048 to the dense path
    # two of the expert layer's 4,096-token chunks
    mellum_shape: Tuple[int, int] = (2, 4096)
    seed: int = 0


FULL = Sizes(
    on_chip=True, train_model="gpt345m", train_shape=(8, 1024),
    train_steps=5, serve_batch=8, page_size=64, max_seq_len=1024,
    prompt_lens=(64, 64, 128, 128, 256, 256, 512, 512), new_tokens=32,
    llama=dict(vocab_size=32000, hidden_size=4096, num_attention_heads=32,
               intermediate_size=11008, max_position_embeddings=4096),
    fused_layers=4, fused_prompt_lens=(32, 48, 64, 96), fused_new_tokens=16,
    hybrid_layers=2, hybrid_shape=(4, 1024), hybrid_steps=3,
    mellum=dict(vocab_size=2048, hidden_size=512, moe_intermediate_size=256,
                num_hidden_layers=4, num_attention_heads=4,
                num_key_value_heads=2, head_dim=128, num_experts=8,
                router_experts=16, first_expert=8, num_experts_per_tok=4,
                sliding_window=512))


# what JAX's persistent compilation cache did since the last phase line:
# a second run in the same checkout must show misses == 0
_CACHE_EVENTS = {
    "/jax/compilation_cache/compile_requests_use_cache": "requests",
    "/jax/compilation_cache/cache_hits": "hits",
    "/jax/compilation_cache/cache_misses": "misses",
}
_cache_counts = collections.Counter()
_listening = False


def _count_cache_event(event: str, **_kw) -> None:
    if event in _CACHE_EVENTS:
        _cache_counts[_CACHE_EVENTS[event]] += 1


def _listen_to_cache() -> None:
    global _listening
    import jax.monitoring
    if not _listening:      # a listener cannot be taken back: add it once
        jax.monitoring.register_event_listener(_count_cache_event)
        _listening = True
    _cache_counts.clear()


def _emit(phase: str, **facts) -> dict:
    line = {"phase": phase, **facts,
            "compile_cache": {k: _cache_counts[k]
                              for k in _CACHE_EVENTS.values()}}
    _cache_counts.clear()
    print(json.dumps(line), flush=True)
    return line


def _has_kernel(text: str) -> bool:
    return "tpu_custom_call" in text


def _amp(on_chip):
    import paddle_tpu as paddle
    return paddle.amp.auto_cast(enable=on_chip, level="O1", dtype="bfloat16")


# ---------------------------------------------------------------- device
def phase_device(s: Sizes) -> dict:
    import jax

    import paddle_tpu as paddle
    from paddle_tpu.utils.metrics import detect_peak_flops

    paddle.run_check()
    dev = jax.devices()[0]
    x = paddle.to_tensor(np.ones((8, 128), np.float32))
    # the array's ACTUAL device, never the Place label
    on = {d.platform for d in x._value.devices()}
    facts = dict(platform=dev.platform, device_kind=dev.device_kind,
                 count=len(jax.devices()), get_device=paddle.get_device(),
                 tensor_place=repr(x.place), tensor_platforms=sorted(on),
                 peak_flops=detect_peak_flops())
    if s.on_chip:
        assert on == {"tpu"}, on
        assert isinstance(x.place, paddle.TPUPlace), x.place
        assert paddle.get_device().startswith("tpu:"), paddle.get_device()
        assert facts["peak_flops"], "peak table does not resolve this chip"
    return _emit("device", **facts)


# ----------------------------------------------------------------- train
def phase_train(s: Sizes):
    """Returns (facts, model) with the trained weights synced back into
    ``model`` for the serve phase."""
    import jax

    import paddle_tpu as paddle

    cfg, batch, seq, build, on_tpu = build_train_setup(s.train_model)
    assert (batch, seq) == s.train_shape, (batch, seq)
    assert on_tpu == s.on_chip
    model, step = build(False)
    rng = np.random.default_rng(s.seed)
    ids = rng.integers(0, cfg.vocab_size, (batch, seq + 1))
    staged = step.stage(paddle.to_tensor(ids[:, :-1].astype(np.int32)),
                        paddle.to_tensor(ids[:, 1:].astype(np.int32)))

    losses, times = [], []
    for _ in range(s.train_steps):
        t0 = time.perf_counter()
        with _amp(on_tpu):
            loss = step(staged)
        losses.append(float(loss))          # the host pull closes the step
        times.append(time.perf_counter() - t0)
    assert all(np.isfinite(losses)), losses
    assert losses[-1] < losses[0], f"loss did not fall: {losses}"
    traces = step.trace_count
    assert traces == 1, traces

    # which attention the compiled step took: the Pallas flash kernel
    # shows as a tpu_custom_call, the dense einsum does not
    with _amp(on_tpu):
        text = step.lower(staged).as_text()
    if s.on_chip:
        assert _has_kernel(text), "train step lowered without a Pallas call"
    stats = jax.devices()[0].memory_stats() or {}
    step.sync_to_model()       # training donated the old param buffers
    steady = float(np.median(times[1:]))
    facts = _emit(
        "train", model=s.train_model, batch=batch, seq=seq,
        n_params=int(sum(p.size for p in model.parameters())),
        losses=[round(v, 4) for v in losses], traces=traces,
        flash_custom_call=_has_kernel(text),
        compile_s=round(times[0] - steady, 2),
        steps_s=round(sum(times[1:]), 3),
        peak_bytes_in_use=stats.get("peak_bytes_in_use"))
    return facts, model


# ----------------------------------------------------------------- serve
def _serve(model, prompts, new_tokens, *, flag: str, value, **engine_kw):
    """Run ``prompts`` through a fresh engine TWICE under ``flag=value``:
    the first run compiles, the second must not. Returns (engine, tokens
    by request, seconds of each run). A dispatch that fails is not
    replayed here: any recovery attempt raises with the cause chained."""
    from paddle_tpu import flags
    from paddle_tpu.generation.program_cache import decode_program_cache
    from paddle_tpu.generation.serving import ServingEngine

    def refuse_recovery(exc):
        raise AssertionError("a serving dispatch failed") from exc

    def trace_counts():
        cache = decode_program_cache()
        return {k: cache.trace_count(k) for k in cache.keys()}

    prior = flags.get_flag(flag)
    flags.set_flags({flag: value})
    try:
        eng = ServingEngine(model, **engine_kw)
        eng._recover_dispatch = refuse_recovery
        runs, secs, traces = [], [], []
        for _ in range(2):
            t0 = time.perf_counter()
            rids = [eng.submit(p, new_tokens) for p in prompts]
            out = eng.run()
            secs.append(time.perf_counter() - t0)
            assert [eng.status(r) for r in rids] == ["OK"] * len(rids), \
                eng.statuses()
            runs.append([out[r] for r in rids])
            traces.append(trace_counts())
    finally:
        flags.set_flags({flag: prior})
    assert runs[0] == runs[1], "the same requests decoded differently twice"
    assert all(len(t) == new_tokens for t in runs[0])
    assert traces[0] == traces[1], "a program retraced after warm-up"
    return eng, runs[0], secs


def _first_split(a, b) -> Optional[int]:
    for i, (ta, tb) in enumerate(zip(a, b)):
        if ta != tb:
            return i
    return None


def _agree(model, prompts, got, want, what: str) -> list:
    """Token-for-token agreement of two greedy decodes. f32 is exact; in
    bf16 on the chip a near-tie may flip an argmax, after which the two
    contexts differ and nothing further is comparable. So at a request's
    FIRST differing position, score the shared context with the model's
    own forward and accept only a tie inside the documented bf16
    tolerance. Returns the near-ties, each printed."""
    import paddle_tpu as paddle
    ties = []
    for r, (prompt, a, b) in enumerate(zip(prompts, got, want)):
        pos = _first_split(a, b)
        if pos is None:
            continue
        ctx = np.concatenate([prompt, np.asarray(a[:pos], np.int32)])
        logits = np.asarray(
            model(paddle.to_tensor(ctx[None].astype(np.int32)))
            ._value[0, -1], np.float32)
        la, lb = float(logits[a[pos]]), float(logits[b[pos]])
        tie = dict(vs=what, request=r, position=pos, tokens=[a[pos], b[pos]],
                   logits=[la, lb], top=float(logits.max()))
        print(json.dumps({"near_tie": tie}), flush=True)
        assert abs(la - lb) <= BF16_ATOL + BF16_RTOL * max(abs(la), abs(lb)), \
            f"tokens differ beyond a bf16 near-tie: {tie}"
        ties.append(tie)
    return ties


def phase_serve(model, s: Sizes) -> dict:
    import paddle_tpu as paddle
    from paddle_tpu.generation.program_cache import decode_program_cache

    model.eval()
    rng = np.random.default_rng(s.seed + 1)
    vocab = model.config.vocab_size
    prompts = [rng.integers(0, vocab, (n,)).astype(np.int32)
               for n in s.prompt_lens]
    kw = dict(max_batch=s.serve_batch, page_size=s.page_size,
              max_seq_len=s.max_seq_len, prefill_chunk=s.prefill_chunk)

    eng, toks, secs = _serve(model, prompts, s.new_tokens,
                             flag="use_pallas", value=True, **kw)
    assert max(s.prompt_lens) > eng.chunk and eng.chunk_dispatches > 0, \
        "chunked prefill never ran"
    cache = decode_program_cache()
    served = set(cache.keys())
    # which programs hold a Pallas kernel, by kind (a short monolithic
    # prefill takes dense attention by the flash shape rule)
    kernels = {}
    for k in sorted(served):
        kernels[k.kind] = (kernels.get(k.kind, True)
                           and _has_kernel(cache.lowered(k).as_text()))
    if s.on_chip:
        assert kernels["prefill_chunk"] and kernels[eng.decode_key.kind], \
            f"serving programs without their paged kernels: {kernels}"

    # the XLA twins: the same engine with every Pallas kernel off
    t0 = time.perf_counter()
    twin, twin_toks, _ = _serve(model, prompts, s.new_tokens,
                                flag="use_pallas", value=False, **kw)
    twin_s = time.perf_counter() - t0
    assert twin.decode_key != eng.decode_key
    assert not any(_has_kernel(cache.lowered(k).as_text())
                   for k in set(cache.keys()) - served), \
        "the XLA-twin engine compiled a Pallas kernel"
    ties = _agree(model, prompts, toks, twin_toks, "xla_twin")

    # model.generate(): one compiled program per (batch, prompt length)
    t0 = time.perf_counter()
    solo = [None] * len(prompts)
    for n in sorted(set(s.prompt_lens)):
        rows = [i for i, p in enumerate(prompts) if len(p) == n]
        out = model.generate(
            paddle.to_tensor(np.stack([prompts[i] for i in rows])),
            max_new_tokens=s.new_tokens, do_sample=False,
            return_full_sequence=False).numpy()
        for i, row in zip(rows, out):
            solo[i] = row.tolist()
    generate_s = time.perf_counter() - t0
    ties += _agree(model, prompts, toks, solo, "generate")

    return _emit(
        "serve", requests=len(prompts), prompt_lens=list(s.prompt_lens),
        new_tokens=s.new_tokens, statuses="OK", recoveries=0,
        decode_kind=eng.decode_key.kind, chunk_dispatches=eng.chunk_dispatches,
        bucket_migrations=eng.bucket_migrations,
        programs=len(served), retraces_after_warmup=0,
        pallas_custom_calls=kernels, near_ties=len(ties),
        compile_s=round(secs[0] - secs[1], 2), steps_s=round(secs[1], 3),
        xla_twin_s=round(twin_s, 2), generate_s=round(generate_s, 2))


# ---------------------------------------------------------- fused decode
def _llama(s: Sizes, layers: int):
    import paddle_tpu as paddle
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM

    paddle.seed(s.seed)
    model = LlamaForCausalLM(LlamaConfig(num_hidden_layers=layers, **s.llama))
    if s.on_chip:
        model.to(dtype="bfloat16")
    return model


def phase_fused_decode(s: Sizes) -> dict:
    from paddle_tpu.generation.program_cache import decode_program_cache

    model = _llama(s, s.fused_layers)
    model.eval()
    rng = np.random.default_rng(s.seed + 2)
    prompts = [rng.integers(0, model.config.vocab_size, (n,)).astype(np.int32)
               for n in s.fused_prompt_lens]
    kw = dict(max_batch=len(prompts), page_size=s.page_size,
              max_seq_len=max(s.fused_prompt_lens) + s.fused_new_tokens
              + s.page_size)

    # default flags: the engine must pick the fused kind on its own
    eng, toks, secs = _serve(model, prompts, s.fused_new_tokens,
                             flag="fused_block_decode", value=True, **kw)
    kind = eng.decode_key.kind
    assert kind.startswith("decode_fused"), kind
    has_kernel = _has_kernel(
        decode_program_cache().lowered(eng.decode_key).as_text())
    if s.on_chip:
        assert has_kernel, "fused decode program without its Pallas kernel"

    plain, plain_toks, _ = _serve(model, prompts, s.fused_new_tokens,
                                  flag="fused_block_decode", value=False,
                                  **kw)
    assert plain.decode_key.kind == "decode_generic", plain.decode_key.kind
    ties = _agree(model, prompts, toks, plain_toks, "unfused")
    return _emit(
        "fused_decode", widths=s.llama, layers=s.fused_layers,
        n_params=int(sum(p.size for p in model.parameters())),
        requests=len(prompts), new_tokens=s.fused_new_tokens, statuses="OK",
        decode_kind=kind, fused_custom_call=has_kernel,
        tokens_equal_unfused="exact" if not ties else "bf16 near-ties",
        near_ties=len(ties), compile_s=round(secs[0] - secs[1], 2),
        steps_s=round(secs[1], 3))


# ------------------------------------- hybrid: Mamba-2 beside attention
def phase_granite_hybrid(s: Sizes) -> dict:
    """The recurrent-state path end to end at the published widths: the
    chunk program carries the state from a full chunk into a padded one
    (whose pad must not move it), decode advances it in place through
    ``ssm_decode_update``, and every token is held against the plain
    reference's argmax by the benchmark's own near-tie rule. (The
    benchmark's check seats prompts of one chunk or less: without this
    phase nothing on the chip compares the carry or the pad mask.)"""
    import os

    import jax
    import jax.numpy as jnp

    from benchmark.lib import hybrid
    from paddle_tpu.generation.program_cache import decode_program_cache

    config = s.granite
    if config is None:
        here = os.path.dirname(os.path.abspath(__file__))
        with open(os.path.join(here, "benchmark", "configs",
                               "granite-4.0-h-micro.json")) as fh:
            config = json.load(fh)
    sysm = hybrid.build_serve_hybrid(config, {}, s.seed + 3, 1)
    eng, ref, model = sysm.engine, sysm.ref, config["model"]
    n, new = s.granite_prompt_len, s.granite_new_tokens
    assert eng.chunk < n < 2 * eng.chunk, (eng.chunk, n)

    def refuse_recovery(exc):
        raise AssertionError("a serving dispatch failed") from exc
    eng._recover_dispatch = refuse_recovery
    rng = np.random.default_rng(s.seed + 3)
    prompt = rng.integers(0, sysm.vocab, (n,)).astype(np.int32)
    t0 = time.perf_counter()
    rid = eng.submit(prompt, new)
    toks = eng.run()[rid]
    serve_s = time.perf_counter() - t0
    assert eng.status(rid) == "OK" and len(toks) == new, eng.statuses()
    assert eng.chunk_dispatches == 2, eng.chunk_dispatches
    has_kernel = "ssm_decode_update" in decode_program_cache().lowered(
        eng.decode_key).as_text()
    if s.on_chip:
        assert has_kernel, "decode program without the state-update kernel"

    ids = jnp.asarray(np.concatenate([prompt, np.asarray(toks, np.int32)]))
    rows = np.asarray(jax.jit(
        lambda w, ids: ref.logits(w, ids, model)[n - 1:n - 1 + new])(
            sysm.weights, ids), np.float32)
    ties = []
    for t, (row, tok) in enumerate(zip(rows, toks)):
        best = int(row.argmax())
        if best == tok:
            continue
        tie = dict(position=t, engine=int(tok), reference=best,
                   gap=float(row[best] - row[tok]), top=float(row[best]))
        print(json.dumps({"near_tie": tie}), flush=True)
        assert tie["gap"] <= ref.TIE_ATOL + ref.TIE_RTOL * abs(tie["top"]), \
            f"a token differs from the reference beyond a near-tie: {tie}"
        ties.append(tie)
    second = np.sort(rows, axis=-1)
    return _emit(
        "hybrid", config=config["name"],
        layers=len(model["layer_types"]),
        n_params=int(sum(p.size for p in sysm.model.parameters())),
        prompt_len=n, chunk=eng.chunk, chunk_dispatches=eng.chunk_dispatches,
        new_tokens=new, tokens=[int(t) for t in toks], statuses="OK",
        decode_kind=eng.decode_key.kind, ssm_update_custom_call=has_kernel,
        state_bytes=eng._state.nbytes, near_ties=len(ties),
        tie_atol=ref.TIE_ATOL,
        # how decided the reference itself was: top logit less the second
        reference_margins=[round(float(v), 5)
                           for v in second[:, -1] - second[:, -2]],
        serve_s=round(serve_s, 2))


# --------------------------- blocks: sparse experts, diffusion over blocks
def phase_sdar_blocks(s: Sizes) -> dict:
    """The block step end to end at the published widths: the prompt's
    whole blocks through a full and a padded block-causal chunk, its
    remainder as the known part of the first block, then denoising and
    commit forwards through the grouped expert matmul and the decode
    paged attention with a block of query rows; every reveal is held
    against the plain reference's by the benchmark's own near-tie rule.
    (The benchmark's check seats prompts of one chunk or less that are
    whole blocks: without this phase nothing on the chip compares the
    chunk carry, the pad or a remainder.)"""
    import os

    from benchmark.lib import blocks, registry, system
    from paddle_tpu.generation.program_cache import decode_program_cache
    from paddle_tpu.kernels import grouped_matmul

    registry.load_all()
    config = s.sdar
    if config is None:
        here = os.path.dirname(os.path.abspath(__file__))
        with open(os.path.join(here, "benchmark", "configs",
                               "sdar-30b-a3b-chat.json")) as fh:
            config = json.load(fh)
    sysm = system.build_serve(config, {}, s.seed + 4, 1)
    eng, ref, model = sysm.engine, sysm.ref, config["model"]
    blen, mask = model["block_length"], model["mask_token_id"]
    n, new = s.sdar_prompt_len, s.sdar_new_tokens
    whole = n - n % blen
    assert eng.chunk < whole < 2 * eng.chunk and n % blen, (eng.chunk, n)

    def refuse_recovery(exc):
        raise AssertionError("a serving dispatch failed") from exc
    eng._recover_dispatch = refuse_recovery
    rng = np.random.default_rng(s.seed + 4)
    prompt = rng.integers(0, mask, (n,)).astype(np.int32)
    t0 = time.perf_counter()
    rid = eng.submit(prompt, new)
    eng.record_blocks([rid])
    toks = eng.run()[rid]
    serve_s = time.perf_counter() - t0
    records = eng.block_records()[rid]
    assert eng.status(rid) == "OK" and len(toks) == new, eng.statuses()
    assert eng.chunk_dispatches == 2, eng.chunk_dispatches
    text = decode_program_cache().lowered(eng.decode_key).as_text()
    kernels = {k: k in text for k in (grouped_matmul.KERNEL_NAME,
                                      "paged_attention")}
    if s.on_chip:
        assert all(kernels.values()), kernels

    rows, ids, cursors, picked = blocks.checked_forwards(
        [(prompt, rid)], {rid: toks}, {rid: records}, blen)
    ties, wrong = blocks.compare(
        rows, blocks.reference_verdict(sysm, ids, cursors, picked), ref)
    for tie in ties:
        print(json.dumps({"near_tie": tie}), flush=True)
    assert not wrong, \
        f"a reveal differs from the reference beyond a near-tie: {wrong}"
    reveals = len(rows)
    hist = eng.expert_histogram()
    return _emit(
        "blocks", config=config["name"], layers=model["num_hidden_layers"],
        n_params=int(sum(p.size for p in sysm.model.parameters())),
        prompt_len=n, chunk=eng.chunk, chunk_dispatches=eng.chunk_dispatches,
        new_tokens=new, tokens=[int(t) for t in toks], statuses="OK",
        forwards=len(records), reveals=reveals, near_ties=len(ties),
        tie_atol=ref.TIE_ATOL, step_kind=eng.decode_key.kind,
        kernels=kernels, experts_touched=int((hist > 0).sum()),
        serve_s=round(serve_s, 2))


# ------------------------------------------------- four chips: dp2 x mp2
def _hybrid_losses(s: Sizes, mesh, x, y):
    """Build the model from the seed, take ``hybrid_steps`` steps on
    ``mesh`` (None: one chip). Returns (losses, sharding facts)."""
    import jax

    import paddle_tpu as paddle
    from paddle_tpu.hapi import TrainStep
    from paddle_tpu.models.llama import annotate_llama_tp

    model = _llama(s, s.hybrid_layers)
    if mesh is not None:
        annotate_llama_tp(model)       # Megatron TP layout as dist_attr
    opt = paddle.optimizer.AdamW(1e-4, parameters=model.parameters(),
                                 weight_decay=0.01,
                                 multi_precision=s.on_chip)
    step = (TrainStep(model, opt) if mesh is None else
            TrainStep(model, opt, mesh=mesh, data_axes=("dp",)))
    staged = step.stage(x, y)
    losses = []
    for _ in range(s.hybrid_steps):
        with _amp(s.on_chip):
            losses.append(float(step(staged)))
    assert all(np.isfinite(losses)), losses
    facts = {}
    if mesh is not None:
        facts = _sharding_facts(step, staged, mesh)
        with _amp(s.on_chip):
            text = step.lower(staged).compile().as_text()
        facts["collectives"] = sorted(
            op for op in ("all-reduce", "reduce-scatter", "all-gather")
            if op in text)
        assert {"all-reduce", "reduce-scatter"} & set(facts["collectives"]), \
            "no reduction collective in the compiled dp2 x mp2 step"
    del step, model, opt, staged
    gc.collect()
    jax.clear_caches()
    return losses, facts


def _sharding_facts(step, staged, mesh) -> dict:
    """Where the state really is. Code that has only ever met one chip
    may have put everything on device 0: look at the shards."""
    n_dev = mesh.devices.size
    mp = mesh.shape["mp"]
    split = 0
    total = per_dev0 = 0
    for name, arr in step.params.items():
        shards = arr.addressable_shards
        total += arr.nbytes
        per_dev0 += sum(sh.data.nbytes for sh in shards
                        if sh.device == mesh.devices.flat[0])
        dims = [i for i, e in enumerate(step.param_shardings[name].spec)
                if e == "mp" or (isinstance(e, tuple) and "mp" in e)]
        if not dims:
            continue
        dim = dims[0]
        assert len({sh.device for sh in shards}) == n_dev, name
        ranges = {(sh.index[dim].start, sh.index[dim].stop) for sh in shards}
        assert len(ranges) == mp, (name, ranges)
        split += 1
    assert split > 0, "no parameter is sharded over mp"
    share = per_dev0 / total
    assert abs(share - 1.0 / mp) < 0.05, \
        f"device 0 holds {share:.3f} of the parameter bytes, not ~1/{mp}"
    batch = staged.vals[0]
    rows = {(sh.index[0].start, sh.index[0].stop)
            for sh in batch.addressable_shards}
    assert len({sh.device for sh in batch.addressable_shards}) == n_dev
    assert len(rows) == mesh.shape["dp"], rows
    return dict(mp_sharded_params=split, param_bytes=int(total),
                device0_param_share=round(share, 4),
                batch_row_ranges=[list(r) for r in sorted(rows)])


def phase_hybrid(s: Sizes) -> dict:
    import jax

    import paddle_tpu as paddle
    from paddle_tpu.distributed.fleet.base_topology import (
        create_hybrid_communicate_group)

    assert len(jax.devices()) >= 4, jax.devices()
    mesh = create_hybrid_communicate_group(dp_degree=2,
                                           mp_degree=2).get_mesh()
    assert len({d.id for d in mesh.devices.flat}) == 4
    batch, seq = s.hybrid_shape
    rng = np.random.default_rng(s.seed + 3)
    ids = rng.integers(0, s.llama["vocab_size"], (batch, seq + 1))
    x = paddle.to_tensor(ids[:, :-1].astype(np.int32))
    y = paddle.to_tensor(ids[:, 1:].astype(np.int32))

    t0 = time.perf_counter()
    sharded, facts = _hybrid_losses(s, mesh, x, y)
    t1 = time.perf_counter()
    single, _ = _hybrid_losses(s, None, x, y)
    t2 = time.perf_counter()
    np.testing.assert_allclose(sharded, single, rtol=BF16_RTOL,
                               atol=BF16_ATOL)
    return _emit(
        "hybrid_dp2_mp2", widths=s.llama, layers=s.hybrid_layers,
        batch=batch, seq=seq, mesh={k: int(v) for k, v in mesh.shape.items()
                                    if v > 1},
        losses_dp2_mp2=[round(v, 4) for v in sharded],
        losses_one_chip=[round(v, 4) for v in single],
        sharded_s=round(t1 - t0, 2), one_chip_s=round(t2 - t1, 2), **facts)


# ------------------- window: window and global layers over two page pools
def phase_trinity_window(s: Sizes) -> dict:
    """The window pool end to end at the published widths: a prompt
    longer than window + chunk through its chunks, the window layers'
    pages before the window given back between them and taken again by
    the same row, then decode through the windowed paged attention; every
    token is held against the plain reference's argmax by the benchmark's
    own near-tie rule, and the row never holds more window pages than
    its bound. (The benchmark's check does the same on four prompts;
    this phase is the one place a single long row is looked at alone,
    page by page.)"""
    import os

    from benchmark.lib import afmoe, registry
    from paddle_tpu.generation.program_cache import decode_program_cache

    registry.load_all()
    config = s.trinity
    if config is None:
        here = os.path.dirname(os.path.abspath(__file__))
        with open(os.path.join(here, "benchmark", "configs",
                               "trinity-mini.json")) as fh:
            config = json.load(fh)
    sysm = afmoe.build_serve_afmoe(config, {}, s.seed + 5, 1)
    eng, ref, model = sysm.engine, sysm.ref, config["model"]
    n, new = s.trinity_prompt_len, s.trinity_new_tokens
    window, caches = model["sliding_window"], eng._caches
    assert n > window + eng.chunk, (n, window, eng.chunk)

    def refuse_recovery(exc):
        raise AssertionError("a serving dispatch failed") from exc
    eng._recover_dispatch = refuse_recovery
    rng = np.random.default_rng(s.seed + 5)
    prompt = rng.integers(0, sysm.vocab, (n,)).astype(np.int32)
    t0 = time.perf_counter()
    rid = eng.submit(prompt, new)
    most = 0
    while eng.has_work():
        eng.run_step()
        most = max(most, len(caches.window.sequence_pages(0)))
    toks = eng.results()[rid]
    serve_s = time.perf_counter() - t0
    assert eng.status(rid) == "OK" and len(toks) == new, eng.statuses()
    assert eng.chunk_dispatches == -(-n // eng.chunk), eng.chunk_dispatches
    span_pages = -(-(n + new) // eng.pool.page_size)
    assert 0 < most <= caches._row_bound < span_pages, (most, span_pages)
    released = caches.window_pages_released
    assert released > 0, released
    assert caches.window.free_page_count() == caches.window.num_pages - 1
    text = decode_program_cache().lowered(eng.decode_key).as_text()
    kernels = {k: k in text for k in ("paged_attention", "gmm")}
    if s.on_chip:
        assert all(kernels.values()), kernels

    checked = [(prompt, toks)]
    ties, wrong, gaps = afmoe.compare(
        [toks], afmoe.deciding_logits(sysm, checked)[:, :new], ref)
    for tie in ties:
        print(json.dumps({"near_tie": tie}), flush=True)
    assert not wrong, \
        f"a token differs from the reference beyond a near-tie: {wrong}"
    hist = eng.expert_histogram()
    return _emit(
        "window", config=config["name"], layers=model["num_hidden_layers"],
        layer_types=model["layer_types"],
        n_params=int(sum(p.size for p in sysm.model.parameters())),
        prompt_len=n, window=window, chunk=eng.chunk,
        chunk_dispatches=eng.chunk_dispatches, new_tokens=new,
        tokens=[int(t) for t in toks], statuses="OK",
        span_pages=span_pages, window_pages_most=most,
        window_row_bound=caches._row_bound, window_pages_released=released,
        pool_pages=[eng.pool.num_pages, caches.window.num_pages],
        near_ties=len(ties), largest_gap=max(gaps, default=0.0),
        tie_atol=ref.TIE_ATOL, decode_kind=eng.decode_key.kind,
        kernels=kernels, experts_touched=int((hist > 0).sum()),
        serve_s=round(serve_s, 2))


# ------------------------------------------------------------ moe_train
def phase_moe_train(s: Sizes) -> dict:
    """The expert layer's and the window's training path on the chip: a
    small Mellum 2 through ``hapi.TrainStep`` (AdamW, O1 autocast) on a
    repeated batch; the loss must fall, the step must not retrace, and on
    the chip its program must hold the windowed flash kernels and the
    grouped products forward and backward (``gmm``, ``tgmm``), so that a
    kernel that no longer lowers is caught before the benchmark."""
    import paddle_tpu as paddle
    from paddle_tpu.hapi import TrainStep
    from paddle_tpu.models import MellumConfig, MellumForCausalLM

    cfg = MellumConfig(**s.mellum)
    paddle.seed(s.seed + 7)
    model = MellumForCausalLM(cfg)
    if s.on_chip:
        model.to(dtype="bfloat16")
    opt = paddle.optimizer.AdamW(1e-3, parameters=model.parameters(),
                                 multi_precision=s.on_chip)
    step = TrainStep(model, opt)
    batch, seq = s.mellum_shape
    rng = np.random.default_rng(s.seed + 7)
    ids = rng.integers(0, cfg.vocab_size, (batch, seq + 1)).astype(np.int32)
    staged = step.stage(ids[:, :-1], ids[:, 1:])
    losses = []
    for _ in range(s.train_steps):
        with _amp(s.on_chip):
            losses.append(float(step(staged)))
    assert all(np.isfinite(losses)), losses
    assert losses[-1] < losses[0], f"loss did not fall: {losses}"
    assert step.trace_count == 1, step.trace_count
    with _amp(s.on_chip):
        text = step.lower(staged).compile().as_text()
    called = [ln.split(" = ", 1)[0] for ln in text.splitlines()
              if 'custom_call_target="tpu_custom_call"' in ln]
    kernels = {k: any(k in c for c in called)
               for k in ("flash_fwd_window", "flash_bwd_dq_window",
                         "flash_bwd_dkv_window", "gmm", "tgmm")}
    if s.on_chip:
        assert all(kernels.values()), kernels
    counts = step.counters()
    assert int(counts["moe_assignments"]) \
        == int(counts["moe_expert_hist"].sum()) > 0
    return _emit(
        "moe_train", layer_types=cfg.layer_types, window=cfg.sliding_window,
        experts_held=[cfg.first_expert, cfg.num_experts],
        router=cfg.router_experts, batch=batch, seq=seq,
        losses=[round(v, 4) for v in losses], traces=step.trace_count,
        kernels=kernels,
        moe_assignments=int(counts["moe_assignments"]),
        expert_load_max_over_mean=round(float(
            counts["moe_expert_hist"].max()
            / counts["moe_expert_hist"].mean()), 3))


# ------------------------------------------------------------------ main
def run_phases(s: Sizes, chips: int = 1) -> list:
    """Every phase of the ``chips`` mode at sizes ``s``; raises on the
    first failed check. Returns the phase lines."""
    _listen_to_cache()
    if chips == 4:
        return [phase_hybrid(s)]
    lines = [phase_device(s)]
    facts, model = phase_train(s)
    lines += [facts, phase_serve(model, s)]
    # the program cache never evicts, and its generic builders hold the
    # model: drop both before the 7B-width phase needs the memory
    from paddle_tpu.generation.program_cache import clear_decode_program_cache
    clear_decode_program_cache()
    del model
    gc.collect()
    lines.append(phase_fused_decode(s))
    clear_decode_program_cache()
    gc.collect()
    lines.append(phase_granite_hybrid(s))
    clear_decode_program_cache()
    gc.collect()
    lines.append(phase_sdar_blocks(s))
    clear_decode_program_cache()
    gc.collect()
    lines.append(phase_trinity_window(s))
    clear_decode_program_cache()
    gc.collect()
    lines.append(phase_moe_train(s))
    return lines


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: only the dp2 x mp2 hybrid step and its "
                         "one-chip twin")
    args = ap.parse_args(argv)

    use_compile_cache()         # before jax is imported
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise RuntimeError(
            f"chip_smoke needs a TPU; JAX found {devices[0].platform!r} "
            f"({devices[0].device_kind})")
    if len(devices) < args.chips:
        raise RuntimeError(f"--chips {args.chips} but JAX found "
                           f"{len(devices)} device(s)")
    run_phases(FULL, chips=args.chips)
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
