"""Putting the system under test together: weights made on the device
from the seed, then the program's own entry points (``hapi.TrainStep``,
``ServingEngine``) exactly as a user calls them. The recipe is a copy of
``bench.py``'s ``build_train_setup`` (bf16 parameters, fp32 master
weights, AdamW, O1 autocast): the original stays where it is.
"""

import importlib.util
import os
import time

import numpy as np

from .registry import ARCHS, builder, lookup

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
INIT_STD = 0.02


def load_reference(config_name: str):
    """``benchmark/refs/<config>.py`` as a module (its name need not be
    an identifier)."""
    path = os.path.join(HERE, "refs", config_name + ".py")
    spec = importlib.util.spec_from_file_location(
        "benchmark_ref_" + config_name.replace("-", "_").replace(".", "_"),
        path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def seed_key(seed: int):
    """A PRNG key from any whole number (the driver's seeds pass 2**31)."""
    import jax
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              seed >> 31)


def lazy_model(config: dict):
    """The program's model object with meta parameters (shape and dtype,
    no bytes), retyped to the dtype it is trained and served in."""
    import dataclasses

    import paddle_tpu as paddle
    from paddle_tpu import models
    cfg_cls = getattr(models, config["program"]["config_class"])
    model_cls = getattr(models, config["program"]["model_class"])
    fields = {f.name for f in dataclasses.fields(cfg_cls)}
    cfg = cfg_cls(**{k: v for k, v in config["model"].items() if k in fields})
    with paddle.LazyGuard():
        model = model_cls(cfg)
    model.to(dtype=config["dtype"])
    return cfg, model


def make_weights(model, seed: int, shardings=None) -> dict:
    """Every parameter in ONE jitted call from the seed, on the device,
    in the dtype it is used in: matrices normal(0, 0.02), norm scales
    one, biases zero. Loads them into ``model`` and returns them."""
    import jax
    import jax.numpy as jnp

    specs = {name: (tuple(p._value.shape), p._value.dtype)
             for name, p in model.named_parameters()}
    names = sorted(specs)

    def make(key):
        out = {}
        for i, name in enumerate(names):
            shape, dtype = specs[name]
            if len(shape) >= 2:
                w = INIT_STD * jax.random.normal(
                    jax.random.fold_in(key, i), shape, jnp.float32)
                out[name] = w.astype(dtype)
            elif name.endswith("bias"):
                out[name] = jnp.zeros(shape, dtype)
            else:
                out[name] = jnp.ones(shape, dtype)
        return out

    jitted = (jax.jit(make) if shardings is None
              else jax.jit(make, out_shardings=shardings))
    weights = jitted(seed_key(seed))
    model.load_raw_state(weights)
    return weights


class Phases:
    """Where set-up goes: seconds since the last mark, by name. For the
    notes of the result line, not for a metric."""

    def __init__(self):
        self.seconds = {}
        self._t = time.perf_counter()

    def mark(self, name: str):
        now = time.perf_counter()
        self.seconds[name] = self.seconds.get(name, 0.0) + now - self._t
        self._t = now


def sizes_of(config: dict) -> dict:
    return lookup(ARCHS, config["arch"], "arch")(config["model"])


def autocast():
    import paddle_tpu as paddle
    return paddle.amp.auto_cast(enable=True, level="O1", dtype="bfloat16")


class TrainSystem:
    def __init__(self, config, traffic, seed, mesh=None):
        import jax
        import paddle_tpu as paddle
        from paddle_tpu.hapi import TrainStep

        self.phases = Phases()
        self.config, self.traffic, self.seed = config, traffic, int(seed)
        self.sizes = sizes_of(config)
        self.batch, self.seq = int(traffic["batch"]), int(traffic["seq_len"])
        self.vocab = int(config["model"]["vocab_size"])
        self.cfg, self.model = lazy_model(config)
        shardings = None
        if mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec as P
            annotate = config["program"].get("annotate")
            if annotate:
                mod, fn = annotate.rsplit(".", 1)
                getattr(importlib.import_module(mod), fn)(self.model)
            shardings = {
                name: NamedSharding(mesh, getattr(p, "dist_attr", None) or P())
                for name, p in self.model.named_parameters()}
        self.phases.mark("import_and_model")
        self.weights = make_weights(self.model, self.seed, shardings)
        jax.block_until_ready(self.weights)
        self.phases.mark("weights")
        self._rng = np.random.default_rng([self.seed, 11])
        self.first_ids = self.next_ids()
        # the plain reference's loss on the first batch, from the same
        # weights, BEFORE the step exists (its first call donates them)
        ref = load_reference(config["name"])
        self.ref = ref
        self.ref_loss = float(jax.jit(
            lambda w, ids: ref.loss(w, ids, config["model"]))(
                self.weights, self.first_ids))
        self.phases.mark("reference_loss")
        opt = paddle.optimizer.AdamW(
            1e-4, parameters=self.model.parameters(), weight_decay=0.01,
            multi_precision=True)
        self.step = (TrainStep(self.model, opt) if mesh is None else
                     TrainStep(self.model, opt, mesh=mesh,
                               data_axes=("dp",)))
        self.weights = None                 # the step owns them now
        self.phases.mark("train_step_built")
        self.devices = (list(mesh.devices.flat) if mesh is not None
                        else jax.devices()[:1])

    def next_ids(self) -> np.ndarray:
        """A fresh batch of token ids from the host-side iterator."""
        return self._rng.integers(0, self.vocab, (self.batch, self.seq + 1),
                                  dtype=np.int64).astype(np.int32)

    def stage(self, ids: np.ndarray):
        return self.step.stage(np.ascontiguousarray(ids[:, :-1]),
                               np.ascontiguousarray(ids[:, 1:]))


@builder("train")
def build_train(config, traffic, seed, chips):
    return TrainSystem(config, traffic, seed)


@builder("train_mesh")
def build_train_mesh(config, traffic, seed, chips):
    from paddle_tpu.distributed.fleet.base_topology import (
        create_hybrid_communicate_group)
    degrees = {f"{k}_degree": int(v) for k, v in traffic["mesh"].items()}
    need = int(np.prod(list(degrees.values())))
    if need != chips:
        raise ValueError(f"mesh {traffic['mesh']} needs {need} chips, the "
                         f"cell asks for {chips}")
    mesh = create_hybrid_communicate_group(**degrees).get_mesh()
    return TrainSystem(config, traffic, seed, mesh=mesh)


class ServeSystem:
    def __init__(self, config, traffic, seed):
        import jax
        from paddle_tpu.generation.serving import ServingEngine

        self.phases = Phases()
        self.config, self.traffic, self.seed = config, traffic, int(seed)
        self.sizes = sizes_of(config)
        self.vocab = int(config["model"]["vocab_size"])
        self.cfg, self.model = lazy_model(config)
        self.phases.mark("import_and_model")
        self.weights = make_weights(self.model, self.seed)
        jax.block_until_ready(self.weights)
        self.phases.mark("weights")
        self.model.eval()
        self.ref = load_reference(config["name"])
        self.engine = ServingEngine(self.model, **config["serve"])
        self.phases.mark("engine_built")
        self.devices = jax.devices()[:1]


@builder("serve")
def build_serve(config, traffic, seed, chips):
    return ServeSystem(config, traffic, seed)
