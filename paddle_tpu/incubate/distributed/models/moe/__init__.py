"""Mixture-of-Experts with expert parallelism.

Reference: python/paddle/incubate/distributed/models/moe/ (``MoELayer``,
``gate/`` with Naive/GShard/Switch gates, capacity utilities, and the
``global_scatter``/``global_gather`` all-to-all CUDA ops — SURVEY.md §2.2
"MoE (incubate)" and §2.1 "Collective ops").

TPU-native design: the reference's dynamic scatter/gather over ragged
per-expert token counts becomes the GShard static-capacity formulation —
one-hot dispatch/combine einsums with a fixed expert capacity, fully
differentiable and shape-static so XLA tiles it onto the MXU and inserts
the token<->expert all-to-all from shardings (experts sharded over a mesh
axis, tokens over dp).

Serving takes the other formulation: :class:`DroplessMoE`
(``dropless.py``) sorts tokens by expert and runs one grouped matmul a
projection, with no capacity and no dropped token.
"""

from .dropless import DroplessMoE, dropless_moe  # noqa: F401
from .gate import BaseGate, GShardGate, NaiveGate, SwitchGate  # noqa: F401
from .moe_layer import Experts, MoELayer, top_k_dispatch  # noqa: F401
