"""Partition specs: how every tensor in the system shards over the mesh.

Mesh axes (distributed/mesh_utils.py): ``("data", "model")`` single-pod,
``("pod", "data", "model")`` multi-pod. Policy:

* **DP**   — batch over ``(pod, data)``.
* **TP**   — attention heads / FFN hidden / vocab over ``model``.
* **EP**   — MoE experts over ``model``; dispatch capacity over ``data``.
* **SP**   — KV-cache *sequence* over ``model`` (flash-decoding with
  distributed LSE — decode attention reduces over the sharded seq axis and
  XLA inserts the LSE-style all-reduce). This is what makes 32k×128 and
  524k×1 caches fit per-chip HBM; see DESIGN.md §4.
* **ZeRO** — optimizer moments additionally sharded over ``data`` on the
  largest evenly-divisible dim (``zero_shard``).

Everything is expressed as ``PartitionSpec`` factories parameterized by the
axis names actually present, so the same policy serves both meshes.
"""

from __future__ import annotations

import dataclasses

from jax.sharding import PartitionSpec as P


@dataclasses.dataclass(frozen=True)
class ShardingPolicy:
    """Axis-name bundle + spec factories for the LM family."""

    data_axes: tuple[str, ...] = ("data",)  # ("pod","data") on multi-pod
    model_axis: str | None = "model"
    shard_kv_seq: bool = True  # SP for KV caches (decode)

    # -- helpers -------------------------------------------------------------
    @property
    def dp(self):
        return self.data_axes if len(self.data_axes) > 1 else self.data_axes[0]

    @property
    def tp(self):
        return self.model_axis

    # -- LM params (stacked layers: leading dim L) -----------------------------
    def embed(self) -> P:
        return P(self.tp, None)  # (V, d): vocab over model

    def lm_head(self) -> P:
        return P(None, self.tp)  # (d, V)

    def attn_in(self) -> P:
        return P(None, None, self.tp)  # (L, d, H*dh): heads over model

    def attn_out(self) -> P:
        return P(None, self.tp, None)  # (L, H*dh, d)

    def ffn_in(self) -> P:
        return P(None, None, self.tp)  # (L, d, ff)

    def ffn_out(self) -> P:
        return P(None, self.tp, None)  # (L, ff, d)

    def norm(self) -> P:
        return P(None, None)  # (L, d) replicated

    def moe_router(self) -> P:
        return P(None, None, None)  # (L, d, E): replicated (tiny)

    def moe_expert_in(self) -> P:
        return P(None, self.tp, None, None)  # (L, E, d, ff): EP

    def moe_expert_out(self) -> P:
        return P(None, self.tp, None, None)  # (L, E, ff, d): EP

    # -- activations ------------------------------------------------------------
    def tokens(self) -> P:
        return P(self.dp, None)  # (B, S)

    def activations(self) -> P:
        return P(self.dp, None, None)  # (B, S, d)

    def logits(self) -> P:
        return P(self.dp, None, self.tp)  # (B, S, V)

    def moe_dispatch(self) -> P:
        # (E, C, d): experts over model, capacity over data
        return P(self.tp, self.dp, None)

    # -- KV cache (L, B, S, Hk, dh) ----------------------------------------------
    def kv_cache(self) -> P:
        seq = self.tp if self.shard_kv_seq else None
        return P(None, self.dp, seq, None, None)

    def kv_lengths(self) -> P:
        return P(self.dp)

    # -- retrieval corpus (N, d) --------------------------------------------
    def corpus_rows(self) -> P:
        """Retrieval corpus embeddings: rows over the data axes, dims
        replicated — the layout both ``DenseIndex.sharded_search_fn`` and
        the host-level ``retrieval/sharded.py`` backend partition by, so
        one mesh serves model shards and corpus shards consistently."""
        return P(self.dp, None)


def zero_shard(spec: P, shape: tuple[int, ...], data_axes: tuple[str, ...], axis_sizes: dict[str, int]) -> P:
    """ZeRO-style moment sharding: add the data axes to the first unsharded
    dim whose size divides the data world; fall back to ``spec`` unchanged.
    """
    world = 1
    for a in data_axes:
        world *= axis_sizes[a]
    entries = list(spec) + [None] * (len(shape) - len(spec))
    for i, (dim, entry) in enumerate(zip(shape, entries)):
        if entry is None and dim % world == 0 and dim > 0:
            entries[i] = data_axes if len(data_axes) > 1 else data_axes[0]
            return P(*entries)
    return spec


def spec_for_path(path: str, policy: ShardingPolicy) -> P:
    """Map a param pytree path (joined by '/') to its PartitionSpec."""
    leaf = path.split("/")[-1]
    table = {
        "embed": policy.embed(),
        "lm_head": policy.lm_head(),
        "wq": policy.attn_in(),
        "wk": policy.attn_in(),
        "wv": policy.attn_in(),
        "wo": policy.attn_out(),
        "w_gate": policy.ffn_in(),
        "w_up": policy.ffn_in(),
        "w_down": policy.ffn_out(),
        "router": policy.moe_router(),
        "e_gate": policy.moe_expert_in(),
        "e_up": policy.moe_expert_in(),
        "e_down": policy.moe_expert_out(),
        "scale": policy.norm(),
        "final_scale": P(None),
    }
    return table.get(leaf, P())
