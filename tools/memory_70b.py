"""Generate MEMORY_70B.md: the north-star Llama-2-70B program build
(stage3 + mp x pp on a simulated v5p-128) — sharding table + per-device
resident-state accounting + lowering evidence. Run under the test env:

  JAX_PLATFORMS=cpu python tools/memory_70b.py
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import toolenv  # noqa: E402


def main():
    toolenv.force_cpu()
    import jax
    import numpy as np
    import jax.numpy as jnp
    from jax.sharding import AbstractMesh

    import paddle_tpu as paddle
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLMPipe
    from paddle_tpu.optimizer import AdamW
    from paddle_tpu.distributed.fleet.meta_parallel.pipeline_parallel import (
        PipelineTrainStep, _STACK_PREFIX)

    dp, pp, mp, M = 2, 8, 8, 8
    cfg = LlamaConfig.llama2_70b()
    with paddle.LazyGuard():
        pipe = LlamaForCausalLMPipe(cfg, num_stages=pp, tensor_parallel=True)
    n_params = sum(int(np.prod(p.shape)) for p in pipe.parameters())
    mesh = AbstractMesh((dp, pp, mp), ("dp", "pp", "mp"))
    opt = AdamW(1e-4, parameters=pipe.parameters(), weight_decay=0.1,
                multi_precision=True)
    step = PipelineTrainStep(pipe, opt, mesh, num_microbatches=M,
                             remat=True, sharding_level=3,
                             sharding_axis="dp", abstract=True,
                             param_dtype=jnp.bfloat16)

    # per-device resident state via the shared memwatch shard
    # accounting (observability/memory.sharded_param_bytes)
    by = step.per_device_state_bytes()
    b, s = 16, 4096
    text = step.lower(jax.ShapeDtypeStruct((b, s), jnp.int32),
                      jax.ShapeDtypeStruct((b, s), jnp.int32)).as_text()

    rows = []
    for k in sorted(step.params):
        sds = step.params[k]
        spec = step.param_shardings[k].spec
        ospec = step.opt_shardings[k].spec
        rows.append((k, tuple(sds.shape), str(sds.dtype), str(spec),
                     str(ospec)))

    gb = lambda x: x / 1e9
    out = []
    out.append("# MEMORY_70B — north-star program build evidence\n")
    out.append("Llama-2-70B (`LlamaConfig.llama2_70b()`, "
               f"**{n_params/1e9:.2f}B params**) lowered as ONE jitted "
               "train step — GroupSharded **stage3** + **mp=8 TP** x "
               "**pp=8 pipeline** x **dp=2**, bf16 params + f32 AdamW "
               "master weights, remat on — over a simulated **TPU v5p-128** "
               "(`AbstractMesh((2, 8, 8), ('dp', 'pp', 'mp'))`), lowered "
               "for the real `tpu` platform from a CPU host.\n")
    out.append("Reproduce: `tests/test_llama70b.py` (runs in ~2 s: "
               "LazyGuard meta params mean the 70B program is built "
               "without allocating a single parameter byte).\n")
    out.append("## Per-device resident state (from the sharding table)\n")
    out.append("| component | bytes/device | GB |")
    out.append("|---|---|---|")
    for key in ("params", "slots", "master", "total"):
        out.append(f"| {key} | {by[key]:,} | {gb(by[key]):.2f} |")
    out.append("")
    out.append(f"v5p HBM: 95 GB/chip -> resident state is "
               f"**{by['total']/95e9*100:.1f}%** of HBM; the rest is "
               "activation/remat headroom. Perfect 128-way sharding of the "
               f"14 bytes/param state would be {14*n_params/128/1e9:.2f} "
               "GB/device.\n")
    out.append("## Lowering evidence\n")
    n_cp = text.count("collective_permute")
    out.append(f"- StableHLO module: {len(text):,} chars, "
               f"mesh `{'dp=2, pp=8, mp=8'}`, "
               f"`num_partitions = 128` present: "
               f"{'num_partitions = 128' in text}")
    out.append(f"- sharding annotations: sdy={'sdy.sharding' in text}, "
               f"collective_permute sites: {n_cp} (0 is expected pre-"
               "partitioning: shardy lowers sharding as `sdy` "
               "annotations and XLA inserts the pp-ring collective-"
               "permutes during SPMD propagation at compile time)")
    out.append(f"- while/scan loops: {text.count('stablehlo.while')}, "
               f"dots: {text.count('stablehlo.dot')}")
    out.append("")
    out.append("## Sharding table (param -> (shape, dtype, param spec, "
               "opt-state spec))\n")
    out.append("| param | shape | dtype | param spec | opt spec |")
    out.append("|---|---|---|---|---|")
    for k, shp, dt, spec, ospec in rows:
        out.append(f"| `{k}` | {shp} | {dt} | `{spec}` | `{ospec}` |")
    out.append("")
    out.append("Stacked decoder blocks (`@stacked.*`) carry the pipeline "
               "stack dim sharded over `pp`, Megatron TP over `mp` "
               "(column: q/k/v/gate/up; row: o/down), and the ZeRO-3 "
               "extension over `dp` — params and optimizer state are "
               "sharded over all 128 chips.\n")

    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "MEMORY_70B.md")
    with open(path, "w") as f:
        f.write("\n".join(out))
    print(f"wrote {path}")
    print({k: f"{gb(v):.2f} GB" for k, v in by.items()})


if __name__ == "__main__":
    main()
