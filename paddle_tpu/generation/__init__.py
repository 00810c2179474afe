"""Autoregressive generation: the inference/decode half of the framework.

Reference parity targets (SURVEY.md §3.5):
  - paddle/fluid/operators/fused/fused_multi_transformer_op.cu — the fused
    decode step against a KV cache (here: ``cached_scaled_dot_product_
    attention`` + the per-model ``forward_with_cache`` hooks);
  - PaddleNLP's ``GenerationMixin.generate`` — the user-facing sampling loop.

TPU-native design: the ENTIRE generation — prefill + every decode step +
sampling — is one jitted function. The decode loop is a ``lax.scan`` with a
static trip count over static-shape ring-buffer caches, so XLA compiles one
program per (batch, prompt_len, max_new_tokens) signature and each decode
step costs one device dispatch, not one per op; everything here stays
on-device.

Models opt in by inheriting ``GenerationMixin`` and providing:
  - ``cache_spec() -> [(num_kv_heads, head_dim), ...]`` (one per layer)
  - ``forward_with_cache(input_ids, caches, offset) -> (logits, caches)``
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

__all__ = ["GenerationMixin"]

_NEG_INF = -1e30


def _apply_logit_adjust(lg, seen, step, repetition_penalty, min_new_tokens,
                        eos_token_id):
    """Repetition penalty over already-seen tokens (HF/reference semantics:
    positive logits divide, negative multiply) + the min-length eos mask.
    Shared by the sampling and beam paths. ``seen``: (rows, V) bool."""
    if repetition_penalty != 1.0:
        pen = jnp.where(lg > 0, lg / repetition_penalty,
                        lg * repetition_penalty)
        lg = jnp.where(seen, pen, lg)
    if eos_token_id is not None and min_new_tokens > 0:
        lg = jnp.where(
            (step < min_new_tokens)
            & (jnp.arange(lg.shape[-1]) == eos_token_id)[None, :],
            _NEG_INF, lg)
    return lg


def _top_k_filter(logits: jax.Array, k: int) -> jax.Array:
    kth = jnp.sort(logits, axis=-1)[..., -k][..., None]
    return jnp.where(logits < kth, _NEG_INF, logits)


def _top_p_filter(logits: jax.Array, top_p) -> jax.Array:
    """Nucleus filtering with a traced threshold: keep the smallest prefix of
    descending-prob tokens whose cumulative mass reaches top_p (the first
    token is always kept since the exclusive cumsum starts at 0)."""
    sorted_desc = jnp.sort(logits, axis=-1)[..., ::-1]
    probs = jax.nn.softmax(sorted_desc, axis=-1)
    cum_excl = jnp.cumsum(probs, axis=-1) - probs
    keep = cum_excl < top_p
    cutoff = jnp.min(jnp.where(keep, sorted_desc, jnp.inf),
                     axis=-1, keepdims=True)
    return jnp.where(logits >= cutoff, logits, _NEG_INF)


class GenerationMixin:
    """Adds jit-compiled ``generate`` to a Layer with decode hooks."""

    def init_cache(self, batch: int, max_len: int, dtype=None):
        """Zero ring-buffer KV caches: one (k, v) pair per layer, each
        (batch, max_len, num_kv_heads, head_dim)."""
        if dtype is None:
            dtype = next(iter(self.parameters())).dtype
        return [(jnp.zeros((batch, max_len, hkv, d), dtype),
                 jnp.zeros((batch, max_len, hkv, d), dtype))
                for hkv, d in self.cache_spec()]

    def generate(self, input_ids, max_new_tokens: int = 32,
                 do_sample: bool = False, temperature: float = 1.0,
                 top_k: int = 0, top_p: float = 1.0,
                 eos_token_id: Optional[int] = None,
                 pad_token_id: Optional[int] = None,
                 repetition_penalty: float = 1.0,
                 min_new_tokens: int = 0,
                 num_beams: int = 1,
                 length_penalty: float = 1.0,
                 return_full_sequence: bool = True):
        """Greedy/sampled/beam autoregressive decode. Returns the (B, P + N)
        full sequence Tensor (or (B, N) generated tail when
        ``return_full_sequence=False``). After an ``eos_token_id`` hit a row
        emits ``pad_token_id`` for the remaining steps (shapes stay static).

        ``repetition_penalty`` > 1 divides positive (multiplies negative)
        logits of every token already present in the row (prompt included),
        HF/reference semantics. ``min_new_tokens`` masks ``eos_token_id``
        for the first N steps. ``num_beams`` > 1 switches to beam search
        (greedy over beams; ``do_sample`` must be False), scoring finished
        beams with ``sum(logprobs) / len**length_penalty``."""
        from ..core.tensor import Tensor
        from ..framework.random import next_key
        from ..jit import functional_call

        ids_val = (input_ids._value if isinstance(input_ids, Tensor)
                   else jnp.asarray(input_ids))
        if ids_val.ndim != 2:
            raise ValueError(f"input_ids must be (batch, seq), got "
                             f"{ids_val.shape}")
        b, p = ids_val.shape
        total = p + int(max_new_tokens)
        maxpos = getattr(getattr(self, "config", None),
                         "max_position_embeddings", None)
        if maxpos is not None and total > maxpos:
            raise ValueError(
                f"prompt ({p}) + max_new_tokens ({max_new_tokens}) = {total} "
                f"exceeds max_position_embeddings ({maxpos})")
        if pad_token_id is None:
            pad_token_id = eos_token_id if eos_token_id is not None else 0

        if num_beams > 1 and do_sample:
            raise ValueError("beam search is greedy over beams — "
                             "do_sample=True is not supported with "
                             "num_beams > 1 (reference raises too)")

        was_training = self.training
        self.eval()
        try:
            from ..jit import ensure_live
            params, buffers = self.raw_state()
            ensure_live(params, "call step.sync_to_model() before generate().")
            # only the knobs the selected builder consumes: spurious sig
            # entries would recompile identical programs (seconds on TPU)
            if num_beams > 1:
                sig = ("beam", b, p, int(max_new_tokens), int(num_beams),
                       eos_token_id, pad_token_id, float(length_penalty),
                       float(repetition_penalty), int(min_new_tokens))
            else:
                sig = ("sample", b, p, int(max_new_tokens), bool(do_sample),
                       int(top_k), eos_token_id, pad_token_id,
                       float(repetition_penalty), int(min_new_tokens))
            cache = getattr(self, "_generate_jit_cache", None)
            if cache is None:
                cache = self._generate_jit_cache = {}
            fn = cache.get(sig)
            if fn is None:
                if num_beams > 1:
                    fn = jax.jit(self._build_beam_generate(
                        b, p, int(max_new_tokens), int(num_beams),
                        eos_token_id, pad_token_id, float(length_penalty),
                        float(repetition_penalty), int(min_new_tokens)))
                else:
                    fn = jax.jit(self._build_generate(
                        b, p, int(max_new_tokens), bool(do_sample),
                        int(top_k), eos_token_id, pad_token_id,
                        float(repetition_penalty), int(min_new_tokens)))
                cache[sig] = fn
            toks = fn(params, buffers, ids_val, next_key(),
                      jnp.float32(temperature), jnp.float32(top_p))
        finally:
            if was_training:
                self.train()
        out = jnp.concatenate([ids_val, toks], axis=1) \
            if return_full_sequence else toks
        return Tensor(out, stop_gradient=True)

    def generate_paged(self, input_ids, max_new_tokens: int = 32,
                       page_size: int = 64, num_pages: Optional[int] = None,
                       eos_token_id: Optional[int] = None,
                       pad_token_id: Optional[int] = None,
                       return_full_sequence: bool = True):
        """Greedy decode against a PAGED KV cache (reference:
        block_multihead_attention serving). Unlike ``generate`` (one scan,
        ring buffers), the token loop runs on the host with ONE jitted
        step — the structure real serving needs: between tokens a
        scheduler may admit/evict sequences by editing block tables, and
        the pool is shared across requests. Numerics match ``generate``'s
        greedy path exactly (tested)."""
        from ..core.tensor import Tensor
        from ..jit import ensure_live, functional_call
        from ..kernels.paged_attention import PagedDecodeState, PagedKVCache

        ids_val = (input_ids._value if isinstance(input_ids, Tensor)
                   else jnp.asarray(input_ids))
        b, p = ids_val.shape
        n_new = int(max_new_tokens)
        total = p + n_new
        maxpos = getattr(getattr(self, "config", None),
                         "max_position_embeddings", None)
        if maxpos is not None and total > maxpos:
            raise ValueError(
                f"prompt ({p}) + max_new_tokens ({n_new}) = {total} "
                f"exceeds max_position_embeddings ({maxpos})")
        if n_new == 0:
            return Tensor(ids_val if return_full_sequence
                          else ids_val[:, :0], stop_gradient=True)
        spec = self.cache_spec()
        if num_pages is None:
            num_pages = b * (-(-total // page_size))
        if pad_token_id is None:
            pad_token_id = eos_token_id if eos_token_id is not None else 0

        was_training = self.training
        self.eval()
        try:
            params, buffers = self.raw_state()
            ensure_live(params, "call step.sync_to_model() before "
                                "generate_paged().")
            dtype = jnp.result_type(next(iter(params.values())))
            mgr = PagedKVCache(
                num_layers=len(spec), num_pages=num_pages,
                page_size=page_size, num_kv_heads=spec[0][0],
                head_dim=spec[0][1], max_batch=b, max_seq_len=total,
                dtype=dtype)
            for s_ in range(b):
                mgr.allocate(s_, total)
            bt = jnp.asarray(mgr.block_tables[:b])
            zeros = jnp.zeros((b,), jnp.int32)
            states = [PagedDecodeState(mgr.k_pages[i], mgr.v_pages[i],
                                       bt, zeros)
                      for i in range(len(spec))]

            cache = getattr(self, "_generate_jit_cache", None)
            if cache is None:
                cache = self._generate_jit_cache = {}
            sig = ("paged", b, p, page_size, num_pages)
            fns = cache.get(sig)
            if fns is None:
                def run(params, buffers, ids, states, offset):
                    logits, states = functional_call(
                        self, params, ids, states, offset, buffers=buffers,
                        method="forward_with_cache")
                    return jnp.argmax(
                        logits[:, -1].astype(jnp.float32), axis=-1), states

                # one wrapper serves both phases (S=p and S=1 retrace
                # under the same jit); cached per signature like generate
                fns = cache[sig] = jax.jit(run)
            prefill = step = fns
            tok, states = prefill(params, buffers, ids_val, states,
                                  jnp.int32(0))
            tok = tok.astype(ids_val.dtype)
            toks = [tok]
            finished = ((tok == eos_token_id) if eos_token_id is not None
                        else jnp.zeros((b,), bool))
            for i in range(1, n_new):
                nxt, states = step(params, buffers, tok[:, None], states,
                                   jnp.int32(p + i - 1))
                nxt = nxt.astype(tok.dtype)
                nxt = jnp.where(finished,
                                jnp.asarray(pad_token_id, tok.dtype), nxt)
                if eos_token_id is not None:
                    finished = finished | (nxt == eos_token_id)
                toks.append(nxt)
                tok = nxt
            gen = jnp.stack(toks, axis=1)
        finally:
            if was_training:
                self.train()
        out = (jnp.concatenate([ids_val, gen], axis=1)
               if return_full_sequence else gen)
        return Tensor(out, stop_gradient=True)

    def generate_speculative(self, input_ids, draft_model,
                             max_new_tokens: int = 32,
                             num_speculative_tokens: int = 4,
                             return_full_sequence: bool = True):
        """Greedy speculative decoding (reference ecosystem: PaddleNLP
        speculative/draft-model inference; Leviathan et al.): a small
        ``draft_model`` proposes ``num_speculative_tokens`` tokens per
        round, the target verifies them in ONE cached forward, and the
        longest agreeing prefix plus the target's correction are
        accepted. Greedy speculation is LOSSLESS — the output equals
        ``generate(..., do_sample=False)`` token for token (tested);
        rounds cost one draft pass + one target pass for up to γ+1
        tokens of progress.

        Cache discipline: both models keep static ring buffers; rejected
        positions simply hold garbage k/v beyond the valid length and
        are overwritten by later writes (attention masks at the valid
        length). Round invariants — target cache holds ``seq[:L-1]``,
        draft cache holds ``seq[:L-1]`` too (the draft consumed exactly
        the accepted prefix minus the newest token: ``M = L_old + a``
        and ``L = L_old + a + 1`` keep ``L - M == 1`` every round) — so
        each round is ONE single-token draft feed + g-1 scan proposals
        + ONE (g+1)-token target verify, all from cached compilations.
        Single-sequence only (per-row acceptance lengths diverge in a
        batch); no eos short-circuit (decode runs to max_new_tokens)."""
        import numpy as np

        from ..core.tensor import Tensor
        from ..jit import ensure_live, functional_call

        g = int(num_speculative_tokens)
        ids_val = (input_ids._value if isinstance(input_ids, Tensor)
                   else jnp.asarray(input_ids))
        b, p = ids_val.shape
        if b != 1:
            raise ValueError("generate_speculative supports batch=1 "
                             "(per-row acceptance lengths diverge)")
        n_new = int(max_new_tokens)
        cap = p + n_new + g + 2   # slack: a round may overshoot n_new
        maxpos = getattr(getattr(self, "config", None),
                         "max_position_embeddings", None)
        if maxpos is not None and cap > maxpos:
            raise ValueError(
                f"prompt ({p}) + max_new_tokens ({n_new}) + speculative "
                f"slack ({g + 2}) = {cap} exceeds "
                f"max_position_embeddings ({maxpos})")

        def setup(model):
            params, buffers = model.raw_state()
            ensure_live(params, "call step.sync_to_model() first.")
            dtype = jnp.result_type(next(iter(params.values())))
            caches = [(jnp.zeros((1, cap, hkv, d), dtype),
                       jnp.zeros((1, cap, hkv, d), dtype))
                      for hkv, d in model.cache_spec()]
            return params, buffers, caches

        def build_fns():
            @jax.jit
            def prefill_t(params, buffers, ids, caches):
                logits, caches = functional_call(
                    self, params, ids, caches, jnp.int32(0),
                    buffers=buffers, method="forward_with_cache")
                return jnp.argmax(logits[0, -1].astype(jnp.float32)), caches

            @jax.jit
            def prefill_d(params, buffers, ids, caches):
                _, caches = functional_call(
                    draft_model, params, ids, caches, jnp.int32(0),
                    buffers=buffers, method="forward_with_cache")
                return caches

            @jax.jit
            def draft_round(params, buffers, tok_in, offset, caches):
                """Feed the newest accepted token at ``offset`` (the
                draft's only gap — see the L-M invariant), then propose
                g greedy tokens."""
                logits, caches = functional_call(
                    draft_model, params, tok_in[None, None], caches,
                    offset, buffers=buffers, method="forward_with_cache")
                tok = jnp.argmax(
                    logits[0, -1].astype(jnp.float32)).astype(tok_in.dtype)

                def body(carry, i):
                    tok, caches = carry
                    lg, caches = functional_call(
                        draft_model, params, tok[None, None], caches,
                        offset + 1 + i, buffers=buffers,
                        method="forward_with_cache")
                    nxt = jnp.argmax(
                        lg[0, -1].astype(jnp.float32)).astype(tok.dtype)
                    return (nxt, caches), tok

                (last, caches), emitted = lax.scan(
                    body, (tok, caches), jnp.arange(g - 1, dtype=jnp.int32))
                return jnp.append(emitted, last), caches

            @jax.jit
            def verify_round(params, buffers, chunk, offset, caches):
                """Target forward over [seq[L-1], d1..dg]: greedy picks
                AFTER each prefix."""
                logits, caches = functional_call(
                    self, params, chunk, caches, offset, buffers=buffers,
                    method="forward_with_cache")
                return jnp.argmax(
                    logits[0].astype(jnp.float32), axis=-1), caches

            return prefill_t, prefill_d, draft_round, verify_round

        cache = getattr(self, "_generate_jit_cache", None)
        if cache is None:
            cache = self._generate_jit_cache = {}
        sig = ("spec", p, g, cap)
        entry = cache.get(sig)
        # the jitted fns close over draft_model: rebuild if the caller
        # passes a different draft (identity-checked, not id()-keyed)
        if entry is None or entry[0] is not draft_model:
            entry = (draft_model, build_fns())
            cache[sig] = entry
        prefill_t, prefill_d, draft_round, verify_round = entry[1]

        was_training = (self.training, draft_model.training)
        self.eval()
        draft_model.eval()
        try:
            tp, tb, t_caches = setup(self)
            dp, db, d_caches = setup(draft_model)

            # prompt
            first, t_caches = prefill_t(tp, tb, ids_val, t_caches)
            d_caches = prefill_d(dp, db, ids_val, d_caches)
            np_ids = np.asarray(ids_val)
            idt = ids_val.dtype
            seq = list(np_ids[0])
            seq.append(int(first))
            L = len(seq)     # accepted length; both caches hold seq[:L-1]

            vchunk = np.zeros((1, g + 1), np_ids.dtype)
            while len(seq) - p < n_new:
                props, d_caches = draft_round(
                    dp, db, jnp.asarray(seq[L - 1], idt),
                    jnp.int32(L - 1), d_caches)
                props_np = np.asarray(props)[:g]

                vchunk[0, 0] = seq[L - 1]
                vchunk[0, 1:g + 1] = props_np
                greedy, t_caches = verify_round(
                    tp, tb, jnp.asarray(vchunk, idt), jnp.int32(L - 1),
                    t_caches)
                greedy_np = np.asarray(greedy)

                a = 0
                while a < g and int(props_np[a]) == int(greedy_np[a]):
                    a += 1
                seq.extend([int(x) for x in props_np[:a]])
                seq.append(int(greedy_np[a]))
                L = len(seq)

            gen = jnp.asarray(np.asarray(seq[p:p + n_new],
                                         np_ids.dtype))[None, :]
        finally:
            if was_training[0]:
                self.train()
            if was_training[1]:
                draft_model.train()
        out = (jnp.concatenate([ids_val, gen], axis=1)
               if return_full_sequence else gen)
        return Tensor(out, stop_gradient=True)

    def _build_generate(self, b, p, n_new, do_sample, top_k,
                        eos_token_id, pad_token_id,
                        repetition_penalty=1.0, min_new_tokens=0):
        from ..jit import functional_call

        def adjust(lg, seen, step):
            return _apply_logit_adjust(lg, seen, step, repetition_penalty,
                                       min_new_tokens, eos_token_id)

        def select(logits, key, temperature, top_p, seen, step):
            lg = adjust(logits.astype(jnp.float32), seen, step)
            if not do_sample:
                return jnp.argmax(lg, axis=-1)
            lg = lg / jnp.maximum(temperature, 1e-6)
            if top_k > 0:
                lg = _top_k_filter(lg, top_k)
            lg = _top_p_filter(lg, top_p)
            return jax.random.categorical(key, lg, axis=-1)

        def gen(params, buffers, ids, key, temperature, top_p):
            total = p + n_new
            dtype = jnp.result_type(next(iter(params.values())))
            caches = [(jnp.zeros((b, total, hkv, d), dtype),
                       jnp.zeros((b, total, hkv, d), dtype))
                      for hkv, d in self.cache_spec()]
            track = repetition_penalty != 1.0

            # prefill: writes cache positions [0, p), predicts token p
            logits, caches = functional_call(
                self, params, ids, caches, jnp.int32(0), buffers=buffers,
                method="forward_with_cache")
            # vocab from the logits, NOT self.config: the mixin contract
            # only requires cache_spec + forward_with_cache. The penalty
            # applies to prompt tokens too — HF/reference semantics.
            seen = (jnp.zeros((b, logits.shape[-1]), bool).at[
                        jnp.arange(b)[:, None], ids].set(True)
                    if track else jnp.zeros((b, 1), bool))
            key, sub = jax.random.split(key)
            tok = select(logits[:, -1], sub, temperature, top_p, seen,
                         jnp.int32(0)).astype(ids.dtype)
            if track:
                seen = seen.at[jnp.arange(b), tok].set(True)
            if eos_token_id is not None:
                finished = tok == eos_token_id
            else:
                finished = jnp.zeros((b,), bool)

            def body(carry, step):
                tok, caches, off, key, finished, seen = carry
                logits, caches = functional_call(
                    self, params, tok[:, None], caches, off, buffers=buffers,
                    method="forward_with_cache")
                key, sub = jax.random.split(key)
                nxt = select(logits[:, -1], sub, temperature, top_p, seen,
                             step).astype(tok.dtype)
                nxt = jnp.where(finished, jnp.asarray(pad_token_id, tok.dtype),
                                nxt)
                if track:
                    seen = seen.at[jnp.arange(b), nxt].set(True)
                if eos_token_id is not None:
                    finished = finished | (nxt == eos_token_id)
                return (nxt, caches, off + 1, key, finished, seen), nxt

            (_, _, _, _, _, _), rest = lax.scan(
                body, (tok, caches, jnp.int32(p), key, finished, seen),
                jnp.arange(1, n_new), length=n_new - 1)
            return jnp.concatenate([tok[:, None],
                                    jnp.moveaxis(rest, 0, 1)], axis=1)

        return gen

    def _build_beam_generate(self, b, p, n_new, beams, eos_token_id,
                             pad_token_id, length_penalty,
                             repetition_penalty=1.0, min_new_tokens=0):
        """Beam search as one jitted program (reference: PaddleNLP
        GenerationMixin beam_search). Beams ride the batch dimension of the
        KV caches ((b*beams, ...)), reindexed with take_along_axis at every
        step; finished beams can only extend with pad at zero extra score.
        Final: best beam by sum(logprobs) / len**length_penalty, counting
        tokens up to and including eos."""
        from ..jit import functional_call

        eos = eos_token_id
        pad = pad_token_id if pad_token_id is not None else (
            eos if eos is not None else 0)

        def adjust(lg, seen, step):
            return _apply_logit_adjust(lg, seen, step, repetition_penalty,
                                       min_new_tokens, eos)

        def gen(params, buffers, ids, key, temperature, top_p):
            del key, temperature, top_p   # greedy over beams
            total = p + n_new
            dtype = jnp.result_type(next(iter(params.values())))
            bb = b * beams
            caches = [(jnp.zeros((bb, total, hkv, d), dtype),
                       jnp.zeros((bb, total, hkv, d), dtype))
                      for hkv, d in self.cache_spec()]
            ids_t = jnp.repeat(ids, beams, axis=0)        # (bb, p)
            track = repetition_penalty != 1.0

            logits, caches = functional_call(
                self, params, ids_t, caches, jnp.int32(0), buffers=buffers,
                method="forward_with_cache")
            vocab = logits.shape[-1]     # NOT self.config: mixin contract
            seen = (jnp.zeros((bb, vocab), bool).at[
                        jnp.arange(bb)[:, None], ids_t].set(True)
                    if track else jnp.zeros((bb, 1), bool))
            lp = jax.nn.log_softmax(
                adjust(logits[:, -1].astype(jnp.float32), seen,
                       jnp.int32(0)), axis=-1)            # (bb, V)
            lp = lp.reshape(b, beams, vocab)
            # all beams of a batch row are identical after prefill: keep
            # only beam 0's distribution so the top-k picks DISTINCT tokens
            first = jnp.where(
                (jnp.arange(beams) == 0)[None, :, None], lp[:, :1], _NEG_INF)
            scores, idx = lax.top_k(first.reshape(b, -1), beams)  # (b, beams)
            tok = (idx % vocab).astype(ids.dtype)                 # (b, beams)
            finished = (tok == eos) if eos is not None \
                else jnp.zeros((b, beams), bool)
            lengths = jnp.ones((b, beams), jnp.int32)
            if track:
                seen = seen.at[jnp.arange(bb), tok.reshape(bb)].set(True)

            def body(carry, step):
                tok, caches, off, scores, finished, lengths, seen = carry
                logits, caches = functional_call(
                    self, params, tok.reshape(bb)[:, None], caches, off,
                    buffers=buffers, method="forward_with_cache")
                lp = jax.nn.log_softmax(
                    adjust(logits[:, -1].astype(jnp.float32), seen, step),
                    axis=-1).reshape(b, beams, vocab)
                # finished beams: only pad continues, at zero extra score
                pad_row = jnp.where(jnp.arange(vocab) == pad, 0.0, _NEG_INF)
                lp = jnp.where(finished[:, :, None], pad_row[None, None], lp)
                cand = scores[:, :, None] + lp                # (b, beams, V)
                scores, idx = lax.top_k(cand.reshape(b, -1), beams)
                src = idx // vocab                            # beam origin
                nxt = (idx % vocab).astype(tok.dtype)
                # reorder every per-beam state to the chosen origins
                gather = lambda x: jnp.take_along_axis(x, src, axis=1)
                finished = gather(finished)
                lengths = gather(lengths)
                flat_src = (jnp.arange(b)[:, None] * beams + src).reshape(bb)
                caches = [(k[flat_src], v[flat_src]) for k, v in caches]
                if track:
                    seen = seen[flat_src].at[
                        jnp.arange(bb), nxt.reshape(bb)].set(True)
                lengths = jnp.where(finished, lengths, lengths + 1)
                if eos is not None:
                    finished = finished | (nxt == eos)
                return ((nxt, caches, off + 1, scores, finished, lengths,
                         seen), (nxt, src))

            tok0 = tok                              # position-0 tokens
            carry = (tok, caches, jnp.int32(p), scores, finished, lengths,
                     seen)
            (_, _, _, scores, finished, lengths, _), (steps, origins) = \
                lax.scan(body, carry, jnp.arange(1, n_new), length=n_new - 1)
            # backtrack: follow each final beam's origin chain to rebuild
            # its token sequence ((n_new-1, b, beams) steps/origins)
            def back(carry, xs):
                beam_idx = carry                    # (b, beams) into step t
                step_tok, step_src = xs
                toks = jnp.take_along_axis(step_tok, beam_idx, axis=1)
                beam_idx = jnp.take_along_axis(step_src, beam_idx, axis=1)
                return beam_idx, toks

            init = jnp.tile(jnp.arange(beams)[None], (b, 1))
            first_beam, rev = lax.scan(back, init, (steps, origins),
                                       reverse=True)
            first_tok = jnp.take_along_axis(tok0, first_beam, axis=1)
            seqs = jnp.concatenate([first_tok[None], rev], axis=0)  # (n,b,beams)
            seqs = jnp.moveaxis(seqs, 0, 2)                  # (b, beams, n)
            norm = scores / (lengths.astype(jnp.float32) ** length_penalty)
            best = jnp.argmax(norm, axis=1)                  # (b,)
            out = jnp.take_along_axis(
                seqs, best[:, None, None], axis=1)[:, 0]     # (b, n_new)
            return out

        return gen
