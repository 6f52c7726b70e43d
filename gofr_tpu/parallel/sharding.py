"""Partition specs for the model zoo.

Megatron-style tensor parallelism over the "model" axis:
  - wq / w_gate / w_up: column-parallel (output features sharded)
  - wo / w_down:        row-parallel (input features sharded)
  - embed:          vocab-sharded (logit matmul reduces over model axis)
  - norms:          replicated
Attention projections shard at WHOLE-HEAD granularity only: q/o when the
TP degree divides n_heads, kv when it divides n_kv_heads — with MQA
(Gemma-2B, n_kv_heads=1) KV is replicated, the standard layout, so decode
all-gathers ride ICI only for Q/O. A shard boundary INSIDE a head is not
just unconventional: the rope/attention reshapes it induces were seen to
miscompile under GSPMD on an earlier jax (tiny config at tp=8: logits off
by ~1.0, cache rows off by ~3.5; not re-checked on jax 0.9), and the
Pallas kernels shard whole heads only (ops.attention._head_axes). So
head-indivisible degrees replicate q/o and keep only the MLP/embed
sharded. wkv's output columns
pack heads outermost ([hkv, 2, hd] blocks, transformer._layer_body), so
each TP shard of the flat dim holds whole (k, v) head pairs — never K on
one half of the group and V on the other.

GSPMD inserts the collectives; we only annotate. Specs are pytrees shaped
exactly like the params pytree from models.init_params.
"""

from __future__ import annotations

from typing import Any

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..models.transformer import TransformerConfig


def param_specs(
    cfg: TransformerConfig, mesh: Mesh, *, model_axis: str = "model",
    untied: bool = False,
) -> dict:
    tp = mesh.shape.get(model_axis, 1)
    shard_kv = cfg.n_kv_heads % tp == 0 if tp > 1 else True
    shard_q = cfg.n_heads % tp == 0 if tp > 1 else True
    m = model_axis if tp > 1 else None
    kv = m if shard_kv else None
    q = m if shard_q else None
    extra = {"unembed": P(m, None)} if untied else {}
    # Qwen2-style qkv biases follow their weight's output-column sharding
    bias = (
        {"bq": P(None, q), "bkv": P(None, kv)}
        if getattr(cfg, "qkv_bias", False)
        else {}
    )
    n_experts = getattr(cfg, "n_experts", 0)
    if n_experts > 0:
        # Expert parallelism over the SAME "model" axis (a TP submesh is
        # the EP group): expert-batched [L, E, ...] weights shard on E
        # when the degree divides the expert count, the router stays
        # replicated. Head-granularity attention sharding is unchanged.
        e = m if (tp > 1 and n_experts % tp == 0) else None
        mlp = {
            "w_router": P(None, None, None),
            "w_gate": P(None, e, None, None),
            "w_up": P(None, e, None, None),
            "w_down": P(None, e, None, None),
        }
    else:
        mlp = {
            "w_gate": P(None, None, m),
            "w_up": P(None, None, m),
            "w_down": P(None, m, None),
        }
    return {
        **extra,
        "embed": P(m, None),
        "final_norm": P(None),
        "layers": {
            **bias,
            "attn_norm": P(None, None),
            "wq": P(None, None, q),
            "wkv": P(None, None, kv),
            "wo": P(None, q, None),
            "mlp_norm": P(None, None),
            **mlp,
        },
    }


def mlp_param_specs(params: dict, mesh: Mesh, *, model_axis: str = "model") -> dict:
    """Specs for models.mlp params: alternating column/row parallel (w0
    column, w1 row, …); biases follow their weight's output sharding."""
    tp = mesh.shape.get(model_axis, 1)
    out = {}
    for name in params:
        idx = int(name[1:])
        if tp <= 1:
            out[name] = P() if name.startswith("b") else P(None, None)
        elif name.startswith("w"):
            out[name] = P(None, model_axis) if idx % 2 == 0 else P(model_axis, None)
        else:
            out[name] = P(model_axis) if idx % 2 == 0 else P(None)
    return out


def batch_spec(mesh: Mesh, *, data_axis: str = "data") -> P:
    return P(data_axis if mesh.shape.get(data_axis, 1) > 1 else None)


def kv_specs(
    cfg: TransformerConfig, mesh: Mesh, *, model_axis: str = "model",
    paged: bool = False,
) -> P:
    """PartitionSpec for the serving engine's KV arrays — the slot slab
    [L, slots, rows, hkv, hd] or (``paged``) the block pool as stored,
    [L, n_blocks, block, hkv * hd]: kv-heads at axis 3 either way, and in
    the pool's flat row a head's columns are contiguous, so a shard of
    that axis is whole heads. Sharded along heads when the TP degree
    divides n_kv_heads; REPLICATED under MQA/GQA remainders (the standard
    layout — with one KV head there is nothing to split, and decode
    all-gathers then ride ICI only for Q/O)."""
    tp = mesh.shape.get(model_axis, 1)
    shard = tp > 1 and cfg.n_kv_heads % tp == 0
    heads = model_axis if shard else None
    return P(None, None, None, heads) if paged else P(None, None, None, heads, None)


def replicate_gather(mesh: Mesh):
    """Collective-compute overlap seam (docs/advanced-guide/
    sharded-serving.md): returns a pytree transform that forces every
    leaf to the REPLICATED layout inside a jitted program —
    with_sharding_constraint lowers to an all-gather of the leaf's
    shards over ICI. The sharded decode path calls it on the NEXT
    layer's weight shards from inside the layer scan, one layer ahead
    of use: the gather has no data dependency on the current layer's
    matmul, so XLA's async collectives / latency-hiding scheduler
    overlap the two. Gathered-weight compute is also bit-identical to
    the single-device forward (no partial-product psum, hence no
    reduction-order drift) — the TP==TP1 token-equality tests pin it."""

    def gather(tree):
        return jax.tree.map(
            lambda a: jax.lax.with_sharding_constraint(
                a, NamedSharding(mesh, P())
            ),
            tree,
        )

    return gather


def tp_submeshes(
    cfg: TransformerConfig,
    tp: int,
    *,
    replicas: int | None = None,
    devices: list | None = None,
) -> list[tuple[Mesh, dict]]:
    """Carve the device list into ``replicas`` disjoint tensor-parallel
    submeshes of ``tp`` chips each and pair every mesh with its
    param_specs — the ``meshes=[...]`` input ReplicatedLLMEngine and the
    disaggregated pools take (dp x tp serving from one call). Defaults
    to as many replicas as the devices allow."""
    import numpy as np

    devices = list(devices if devices is not None else jax.devices())
    tp = max(1, int(tp))
    if replicas is None:
        replicas = len(devices) // tp
    if replicas < 1 or replicas * tp > len(devices):
        raise ValueError(
            f"need {max(1, replicas)} replica(s) x tp={tp} = "
            f"{max(1, replicas) * tp} devices, have {len(devices)}"
        )
    out = []
    for i in range(replicas):
        sub = devices[i * tp : (i + 1) * tp]
        mesh = Mesh(np.asarray(sub).reshape(1, tp), ("data", "model"))
        out.append((mesh, param_specs(cfg, mesh)))
    return out


def shard_params(params: Any, mesh: Mesh, specs: Any) -> Any:
    """device_put every leaf with its NamedSharding (committed, so later jit
    calls respect the placement without in_shardings plumbing)."""
    return jax.tree.map(
        lambda x, s: jax.device_put(x, NamedSharding(mesh, s)),
        params,
        specs,
        is_leaf=lambda x: x is None,
    )


def with_shardings(mesh: Mesh, fn, in_specs=None, out_specs=None, **jit_kw):
    """jit fn with NamedSharding-resolved in/out specs (None = infer)."""

    def resolve(tree):
        if tree is None:
            return None
        return jax.tree.map(
            lambda s: NamedSharding(mesh, s) if isinstance(s, P) else s,
            tree,
            is_leaf=lambda x: isinstance(x, P),
        )

    return jax.jit(fn, in_shardings=resolve(in_specs), out_shardings=resolve(out_specs), **jit_kw)
