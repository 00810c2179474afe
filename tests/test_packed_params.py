"""What a serving program's call hands over and gets back.

Every array a compiled program takes or returns is a buffer its call
pays for on the host. The engine hands its programs the vector weights
(norm scales, biases) stacked into one array per ``(dtype, shape)``
(``serving.PackedParams``), and each program returns of the cache
entries only the stores' arrays (``cache_manager.cache_arrays``), never
a block table or cursor. Here: the programs' own argument and result
lists, the gauge that publishes them, the tokens (bitwise those of the
unstacked form and of solo greedy decode), and a sharded vector left
as it is.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec, \
    SingleDeviceSharding

import paddle_tpu as paddle
from paddle_tpu import observability as obs
from paddle_tpu.generation import serving
from paddle_tpu.generation.program_cache import clear_decode_program_cache
from paddle_tpu.models import (GPTConfig, GPTForCausalLM,
                               GraniteHybridConfig, GraniteHybridForCausalLM)

LAYERS = 4
# one prompt under the chunk (a whole-prompt prefill), two over it (a
# padded final chunk each)
LENS = (7, 37, 21)


def perturbed(model, seed):
    """``model`` with every vector weight moved off its initial ones and
    zeros, so that a vector read from the wrong row changes tokens."""
    rng = np.random.default_rng(seed)
    params, _ = model.raw_state()
    model.load_raw_state({
        k: v + jnp.asarray(0.2 * rng.standard_normal(v.shape), v.dtype)
        for k, v in params.items() if v.ndim <= 1})
    model.eval()
    return model


def tiny_gpt():
    paddle.seed(40)
    return perturbed(GPTForCausalLM(GPTConfig(
        vocab_size=128, hidden_size=32, num_hidden_layers=LAYERS,
        num_attention_heads=4, intermediate_size=64,
        max_position_embeddings=128, initializer_range=0.3)), seed=1)


def tiny_granite():
    paddle.seed(41)
    return perturbed(GraniteHybridForCausalLM(GraniteHybridConfig.tiny(
        embedding_multiplier=1.0, initializer_range=0.1)), seed=2)


def engine(model):
    return serving.ServingEngine(model, max_batch=4, page_size=8,
                                 max_seq_len=64, prefill_chunk=16,
                                 bucket_ladder=(4,))


def prompts(vocab):
    rng = np.random.default_rng(7)
    return [rng.integers(0, vocab, (n,)).astype(np.int32) for n in LENS]


def serve(model):
    eng = engine(model)
    rids = [eng.submit(p, 6) for p in prompts(model.config.vocab_size)]
    out = eng.run()
    return eng, [out[r] for r in rids]


def gauge(kind, side):
    fam = obs.registry().snapshot()["metrics"]["serving_program_buffers"]
    return next(s["value"] for s in fam["series"]
                if s["labels"] == {"kind": kind, "side": side})


def vector_groups(model):
    """(dtype, shape) -> how many vector weights of ``model`` have it."""
    groups = {}
    for v in model.raw_state()[0].values():
        if v.ndim <= 1:
            key = (str(v.dtype), tuple(v.shape))
            groups[key] = groups.get(key, 0) + 1
    return groups


@pytest.fixture(scope="module")
def gpt_served():
    """A 4-layer GPT engine through admission, chunked prefill and
    decode, with each program's executables and the gauge as its builds
    left it."""
    clear_decode_program_cache()
    model = tiny_gpt()
    eng, _ = serve(model)
    programs = {"decode_generic": eng._decode_fns[4],
                "prefill_chunk": eng._chunk_fn,
                "prefill": eng._prefill_fn}
    exes = {kind: list(fn._exes.values()) for kind, fn in programs.items()}
    gauges = {(kind, side): gauge(kind, side)
              for kind in programs for side in ("in", "out")}
    return model, exes, gauges


@pytest.mark.parametrize("kind", ["decode_generic", "prefill_chunk",
                                  "prefill"])
def test_programs_take_stacked_vectors_and_return_only_stores(gpt_served,
                                                              kind):
    model, exes, gauges = gpt_served
    assert exes[kind]
    groups = vector_groups(model)
    assert len(groups) == 3            # hidden, q|k|v and fc1 widths
    for exe in exes[kind]:
        params = exe.args_info[0][0]
        assert isinstance(params, serving.PackedParams)
        # one array per (dtype, shape) of the vectors, a row each
        assert sorted((str(a.dtype), tuple(a.shape)) for a in params.stacks) \
            == sorted((d, (n,) + s) for (d, s), n in groups.items())
        assert all(len(a.shape) > 1 for a in params.rest.values())
        assert len(params.rows) == sum(groups.values())
        ins = jax.tree_util.tree_leaves(exe.args_info)
        outs = jax.tree_util.tree_leaves(exe.out_info)
        # the token and each layer's K and V pools: no table, no cursor
        assert len(outs) == 1 + 2 * LAYERS
        assert [o for o in outs if o.dtype == jnp.int32] == outs[:1]
        assert all(o.shape == outs[1].shape for o in outs[1:])
        assert (gauges[kind, "in"], gauges[kind, "out"]) \
            == (len(ins), len(outs))
    # the call in full: 16 matrices and the two embeddings, 3 stacks,
    # the inputs of its kind, 8 pools, the tables and the cursors
    assert gauges[kind, "in"] == 18 + 3 + {"decode_generic": 2,
                                           "prefill_chunk": 2,
                                           "prefill": 1}[kind] + 8 + 2


@pytest.mark.parametrize("kind", ["gpt", "granite"])
def test_stacked_programs_give_the_tokens_of_the_unstacked(kind,
                                                           monkeypatch):
    """The same requests through the engine as it is and through one
    whose programs take every weight as its own leaf (nothing stacked):
    bitwise the same tokens. GPT: admission, a whole-prompt prefill,
    chunked prefill and decode, also against solo greedy decode;
    granite: its recurrent state carried across chunks."""
    model = tiny_gpt() if kind == "gpt" else tiny_granite()
    clear_decode_program_cache()
    try:
        _, stacked = serve(model)
        clear_decode_program_cache()
        monkeypatch.setattr(serving.PackedParams, "pack", classmethod(
            lambda cls, params: cls(dict(params), (), ())))
        eng, leaves = serve(model)
        assert not eng._packed.stacks
    finally:
        clear_decode_program_cache()
    assert stacked == leaves
    assert len({t for out in stacked for t in out}) > 4
    if kind == "gpt":
        for p, out in zip(prompts(model.config.vocab_size), stacked):
            assert out == model.generate(
                paddle.to_tensor(p[None]), max_new_tokens=6,
                do_sample=False,
                return_full_sequence=False).numpy()[0].tolist()


def test_a_sharded_vector_is_left_as_it_is():
    """Only vectors whole on every device they are on are stacked: a
    tensor-parallel engine's sharded bias stays a leaf, a vector alone
    in its group stays one, matrices are never stacked, and what the
    programs see is bitwise what was packed."""
    devs = jax.devices()
    mesh = Mesh(np.array(devs[:2]), ("mp",))
    rep, split = NamedSharding(mesh, PartitionSpec()), \
        NamedSharding(mesh, PartitionSpec("mp"))
    one = SingleDeviceSharding(devs[0])
    rng = np.random.default_rng(3)

    def put(shape, sharding):
        return jax.device_put(
            jnp.asarray(rng.standard_normal(shape), jnp.float32), sharding)
    params = {"mp.bias": put((8,), split), "a.bias": put((8,), rep),
              "b.bias": put((8,), rep), "w.weight": put((8, 8), rep),
              "c.bias": put((8,), one), "d.bias": put((8,), one),
              "e.bias": put((4,), one)}
    packed = serving.PackedParams.pack(params)
    assert sorted(packed.rest) == ["e.bias", "mp.bias", "w.weight"]
    assert sorted(sorted(n for n, i, _ in packed.rows if i == g)
                  for g in range(len(packed.stacks))) \
        == [["a.bias", "b.bias"], ["c.bias", "d.bias"]]
    assert packed.rest["mp.bias"].sharding == split
    unpacked = packed.unpack()
    assert sorted(unpacked) == sorted(params)
    for name, v in params.items():
        np.testing.assert_array_equal(np.asarray(unpacked[name]),
                                      np.asarray(v))
    # a stack stays where its rows were
    for stack in packed.stacks:
        assert stack.sharding.is_fully_replicated
