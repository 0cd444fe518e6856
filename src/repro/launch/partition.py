"""Parameter / optimizer / cache / batch partition specs.

Maps every leaf of the model state onto the production mesh:

  TP   ("model")         — attention projections, FFN hidden, vocab,
                           expert dim (EP) when divisible, SSM heads.
  FSDP ("pod","data")    — d_model dim of the big archs' weights, so
                           params + AdamW state fit the 16 GB/chip HBM.
  batch ("pod","data")   — activations, KV caches (+ "model" over the KV
                           sequence axis for the flash-decoding /
                           back-streaming serving path).

Leaves are classified by name and (stacked) rank, so the same rules cover
the decoder-only, enc-dec, MoE, and hybrid/SSM parameter trees.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.models.config import ArchConfig
from repro.sharding import ShardingRules


def _divisible(n: int, mesh: Mesh, axes) -> bool:
    if not axes:
        return True
    size = 1
    for a in (axes if isinstance(axes, tuple) else (axes,)):
        size *= mesh.shape[a]
    return size > 0 and n % size == 0


@dataclasses.dataclass(frozen=True)
class PartitionPlan:
    rules: ShardingRules
    fsdp: bool              # shard weight d_model dim over ("pod","data")

    @property
    def mesh(self) -> Mesh:
        return self.rules.mesh

    @property
    def tp(self) -> Optional[str]:
        return self.rules.model_axis

    @property
    def fsdp_axes(self) -> Optional[Tuple[str, ...]]:
        return self.rules.batch_axes if self.fsdp else None


def _leaf_spec(plan: PartitionPlan, cfg: ArchConfig, name: str,
               leaf: Any) -> P:
    """Spec for one stacked parameter leaf (leading n_blocks dim for block
    params; embeddings/final norms are unstacked)."""
    mesh, tp, fs = plan.mesh, plan.tp, plan.fsdp_axes
    shape = leaf.shape
    nd = len(shape)

    def ax(dim_size, axes):
        return axes if (axes and _divisible(dim_size, mesh, axes)) else None

    if name == "embed":                                  # (V, D)
        return P(ax(shape[0], tp), None)
    if name in ("ln", "final_ln", "enc_final_ln", "dt_bias", "A_log", "D",
                "conv_b", "norm"):                       # vectors
        return P(*([None] * nd))
    if name == "router":                                 # (nb, d, e)
        return P(*([None] * nd))
    if name in ("wq", "wk", "wv", "w_z", "w_x"):         # (nb, d, out)
        return P(None, ax(shape[1], fs), ax(shape[2], tp))
    if name in ("wo", "out_proj"):                       # (nb, in, d)
        return P(None, ax(shape[1], tp), ax(shape[2], fs))
    # a shared expert's w_gate / w_up / w_down are (nb, d, f) stacks, as
    # a dense FFN's are
    if name in ("w_gate", "w_up"):
        if nd == 4:                                      # MoE (nb, e, d, f)
            if tp and _divisible(shape[1], mesh, tp):    # EP over experts
                return P(None, tp, ax(shape[2], fs), None)
            return P(None, None, ax(shape[2], fs), ax(shape[3], tp))
        return P(None, ax(shape[1], fs), ax(shape[2], tp))   # (nb, d, f)
    if name == "w_down":
        if nd == 4:                                      # MoE (nb, e, f, d)
            if tp and _divisible(shape[1], mesh, tp):
                return P(None, tp, None, ax(shape[3], fs))
            return P(None, None, ax(shape[2], tp), ax(shape[3], fs))
        return P(None, ax(shape[1], tp), ax(shape[2], fs))   # (nb, f, d)
    if name in ("w_B", "w_C", "w_dt"):                   # (nb, d, n)
        return P(None, ax(shape[1], fs), None)
    if name == "conv_w":                                 # (nb, w, di)
        return P(None, None, ax(shape[2], tp))
    return P(*([None] * nd))


def _quant_leaf_spec(plan: PartitionPlan, name: str, leaf: Any) -> P:
    """Spec for one child array of a block-quantized QTensor leaf
    (DESIGN.md §10).  The packed input-block axis cannot be split
    without tearing quant blocks across shards, so only the OUT-COLUMN
    axis (always last, for scales/mins and quants alike) is sharded —
    by tp where the fp rule tensor-parallelized the projection's output,
    by the fsdp axes where the fp rule put the weight's d_model output
    (wo/out_proj/w_down)."""
    mesh, tp, fs = plan.mesh, plan.tp, plan.fsdp_axes
    last = leaf.shape[-1]
    axes = fs if name in ("wo", "out_proj", "w_down") else tp
    if not (axes and _divisible(last, mesh, axes)):
        axes = None
    return P(*([None] * (leaf.ndim - 1) + [axes]))


def param_specs(abstract_params: Any, cfg: ArchConfig,
                plan: PartitionPlan) -> Any:
    """PartitionSpec pytree matching the parameter pytree."""

    def walk(path, leaf):
        name = None
        for entry in reversed(path):
            if isinstance(entry, jax.tree_util.DictKey):
                name = str(entry.key)
                break
        if path and isinstance(path[-1], jax.tree_util.GetAttrKey):
            # QTensor child (scales/quants/mins) of a quantized leaf
            return _quant_leaf_spec(plan, name or "", leaf)
        return _leaf_spec(plan, cfg, name or "", leaf)

    return jax.tree_util.tree_map_with_path(walk, abstract_params)


def opt_state_specs(abstract_opt: Any, p_specs: Any) -> Any:
    """AdamW state mirrors the params: step replicated, mu/nu/master use
    the param specs."""
    import repro.optim.adamw as adamw
    return adamw.OptState(
        step=P(),
        mu=p_specs, nu=p_specs, master=p_specs)


def batch_specs(abstract_batch: Dict[str, Any],
                plan: PartitionPlan) -> Dict[str, P]:
    b_axes = plan.rules.batch_axes
    out = {}
    for k, v in abstract_batch.items():
        spec = [b_axes] + [None] * (len(v.shape) - 1)
        if v.shape[0] == 1 or not _divisible(v.shape[0], plan.mesh, b_axes):
            spec[0] = None                      # batch-1 long-context cells
        out[k] = P(*spec)
    return out


def cache_specs(abstract_cache: Dict[str, Any], cfg: ArchConfig,
                plan: PartitionPlan) -> Dict[str, P]:
    """KV caches sharded (layers, B, KH, S, hd): batch over data axes and
    *sequence* over the model axis — the flash-decoding layout whose
    partial-attention merge is the back-streaming protocol's producer task.
    SSM states shard their head dim over the model axis."""
    mesh, tp = plan.mesh, plan.tp
    b_axes = plan.rules.batch_axes
    out: Dict[str, P] = {}
    for k, v in abstract_cache.items():
        if k == "pos":
            out[k] = P()
            continue
        shape = v.shape
        if len(shape) == 1:
            # per-slot (B,) clocks (enc_pos): batch-sharded like the rows
            # they describe, replicated when the batch doesn't divide
            out[k] = P(b_axes if _divisible(shape[0], mesh, b_axes)
                       else None)
            continue
        if k == "page_table":
            # (B, n_pages) int32 page indices (DESIGN.md §9): rows follow
            # the batch sharding of the KV panels they index; the page
            # axis is tiny and never sharded.  NOTE the pages point into
            # the row's own (S, hd) panel, so sequence-axis (tp) sharding
            # of the panels composes only when pages don't cross shards —
            # the serve loop keeps tables per-row-local.
            out[k] = P(b_axes if _divisible(shape[0], mesh, b_axes)
                       else None, None)
            continue
        batch_ax = b_axes if _divisible(shape[1], mesh, b_axes) else None
        if k.startswith(("kscale", "vscale")):
            # (L, B, KH, n_pages) per-page scales of an int8 KV cache
            # (DESIGN.md §10): batch follows the panels; the page axis
            # must stay whole — each page's scale lives with its page,
            # and sequence (tp) sharding of the int8 panels would split
            # pages across shards anyway, so quantized serving keeps the
            # sequence axis unsharded (the serve path is single-shard)
            out[k] = P(None, batch_ax, None, None)
            continue
        if k.startswith(("k", "v")) and not k.startswith("conv"):
            seq_ax = tp if (tp and _divisible(shape[3], mesh, tp)) else None
            pt = abstract_cache.get("page_table")
            if seq_ax and pt is not None:
                # Pages are the indivisible unit of the paged cache: a
                # (B, n_pages) table maps logical pages to physical pool
                # pages, so a sequence (tp) split of the pool composes
                # ONLY when every page lies wholly inside one shard.  A
                # page straddling a shard boundary would silently read
                # garbage through the kernel's page indirection — fail
                # loudly instead (DESIGN.md §11).
                n_model = mesh.shape[tp]
                page_size = shape[3] // pt.shape[1]
                if page_size == 0 or (shape[3] // n_model) % page_size:
                    raise ValueError(
                        f"cache leaf {k!r}: sequence-axis ({tp}) sharding "
                        f"of the KV panel (S={shape[3]}) over "
                        f"{n_model} shards would split a page "
                        f"(page_size={page_size}) across shards; use a "
                        f"page_size dividing S/{n_model}, fewer model "
                        f"shards, or the head-sharded serving plan "
                        f"(serve_cache_specs)")
            out[k] = P(None, batch_ax, None, seq_ax, None)
        elif k.startswith("cross_"):
            out[k] = P(None, batch_ax, None, None, None)
        elif k.startswith("conv"):
            di_ax = tp if (tp and _divisible(shape[3], mesh, tp)) else None
            out[k] = P(None, batch_ax, None, di_ax)
        elif k.startswith("ssm"):
            nh_ax = tp if (tp and _divisible(shape[2], mesh, tp)) else None
            out[k] = P(None, batch_ax, nh_ax, None, None)
        else:
            out[k] = P(*([None] * len(shape)))
    return out


def serve_head_regime(cfg: ArchConfig, plan: PartitionPlan
                      ) -> Tuple[bool, bool]:
    """(shard_q, shard_kv) for the serving TP plan (DESIGN.md §11).

    A contiguous split of the fused (H*hd) projection column aligns with
    GQA head GROUPS only when the KV heads split with it (n | KH) or when
    every head shares the one KV head (KH == 1, n | H); anything else
    must stay replicated — serving favours a bitwise-identical replicated
    fallback over a reshuffled head order."""
    tp, mesh = plan.tp, plan.mesh
    n = mesh.shape[tp] if tp else 1
    h, kh = cfg.n_heads, cfg.n_kv_heads
    if n <= 1 or h <= 0 or not cfg.has_attention:
        # pure-SSM stacks have head counts but no attention merges —
        # nothing for the head-group shard_map to do
        return False, False
    shard_kv = kh > 0 and kh % n == 0
    shard_q = shard_kv or (kh == 1 and h % n == 0)
    return shard_q, shard_kv


def serve_param_specs(abstract_params: Any, cfg: ArchConfig,
                      plan: PartitionPlan) -> Any:
    """PartitionSpec pytree for SERVING under the bitwise-token contract
    (DESIGN.md §11): every parameter is replicated on the model axis.

    Column-sharding wq/wk/wv looks bitwise-safe on paper (each output
    column is a full-contraction dot), but in practice the partitioned
    gemm's different output width changes the backend's blocking and
    perturbs low mantissa bits — measured ~3e-2 drift on bf16 smoke
    configs.  So the jit-visible program stays fully replicated and the
    model axis is engaged ONLY inside the decode head-group shard_map
    (`backstream._headgroup_gather_decode`), whose in_specs slice whole
    heads out of replicated operands — a pure bit-copy.  The KV cache
    (see `serve_cache_specs`) may still shard its KV-head axis: scatter
    writes into a head-sharded panel are also layout-only."""
    del cfg, plan
    return jax.tree_util.tree_map(
        lambda leaf: P(), abstract_params)


def serve_cache_specs(abstract_cache: Dict[str, Any], cfg: ArchConfig,
                      plan: PartitionPlan) -> Dict[str, P]:
    """Cache specs for SERVING under the bitwise-token contract
    (DESIGN.md §11): batch shards over the data axes when it divides;
    every other axis — KV heads included — stays model-REPLICATED.
    Committing a KV-head sharding here looks free (the head axis is
    batch-like in every attention contraction) but backward sharding
    propagation column-partitions the prefill x@wk / x@wv gemms, which
    changes the backend's blocking and drifts bf16 low bits (measured
    ~3e-2 on smoke configs).  The sequence axis NEVER shards: its
    partial-softmax merge re-associates the reduction and a seq split
    can straddle a page (see the guard in `cache_specs`).  The decode
    head-group shard_map slices KV heads out of the replicated panels
    at its boundary — a bit-copy — so tensor parallelism still divides
    attention compute n ways without touching the jit graph's bits."""
    mesh, tp = plan.mesh, plan.tp
    del tp
    b_axes = plan.rules.batch_axes
    kh_ax = None
    out: Dict[str, P] = {}
    for k, v in abstract_cache.items():
        shape = v.shape
        if k == "pos":
            out[k] = P()
        elif len(shape) == 1:
            out[k] = P(b_axes if _divisible(shape[0], mesh, b_axes)
                       else None)
        elif k == "page_table":
            out[k] = P(b_axes if _divisible(shape[0], mesh, b_axes)
                       else None, None)
        else:
            batch_ax = b_axes if _divisible(shape[1], mesh, b_axes) \
                else None
            if k.startswith(("kscale", "vscale")):
                out[k] = P(None, batch_ax, kh_ax, None)
            elif k.startswith(("k", "v")) and not k.startswith("conv"):
                out[k] = P(None, batch_ax, kh_ax, None, None)
            else:
                out[k] = P(None, batch_ax, *([None] * (len(shape) - 2)))
    return out


def to_shardings(specs: Any, mesh: Mesh) -> Any:
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s), specs,
        is_leaf=lambda x: isinstance(x, P))


def make_plan(cfg: ArchConfig, rules: ShardingRules, *,
              train: bool) -> PartitionPlan:
    """FSDP policy: shard weights over the data axes when params would not
    comfortably fit per chip under TP alone (16 GB HBM v5e).  Training
    triples the pressure with the f32 AdamW state."""
    n = cfg.n_params()
    threshold = 5e9 if train else 60e9       # bytes headroom heuristics
    return PartitionPlan(rules=rules, fsdp=n > threshold)
