"""Sharding policy for the distributed FSA runtime (Section 3.2.1 on a mesh).

The mesh has two kinds of axes:

* **client axes** (``pod``/``data``) — every position is one FSA
  *aggregator*: it owns a disjoint segment of each parameter ("store"
  layout), receives exactly that segment of every client update via
  ``psum_scatter`` (Eq. 2), and runs the shard-local optimizer on it.
* **model axis** — manual-collective tensor parallelism inside each
  client group under the family-generic shard plan
  (``models/shard_plan``): Megatron column/row pairs, vocab-parallel
  embed/unembed, expert-parallel MoE (expert-dim shards + token
  all_to_all), head-/channel-sharded recurrent mixers, and optional
  sequence parallelism.  :class:`TPSpec` (re-exported from the shard
  plan) maps every entry of ``models/transformer.param_spec`` to its
  model-axis shard dim (or replicate/partial); the serving path keeps
  its GSPMD "use" layout.

The segment-of-a-parameter choice is the *scatter dim*: for each leaf we
pick the rightmost dimension OF THE TP-LOCAL SHAPE divisible by the
number of aggregators; a leaf with no such dimension is replicated over
the client axes and aggregated with a full ``psum`` (always correct,
never sharded).  This mirrors the coordinate partition masks of
``repro.core.masks`` at tensor granularity: the set of (leaf, slice)
pairs owned by aggregator ``a`` IS the mask m_(a) — disjoint and
complete by construction (Theorem B.1 applies unchanged; with TP it
applies per model-axis shard).  The "store" layout composes both axes:
``model`` at the TP dim times the client axes at the scatter dim.
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

QBLOCK = 256        # coords per int8-wire scale (kernels/quantize.QBLOCK)


def shard_map(f, mesh: Mesh, in_specs, out_specs):
    """Fully-manual ``jax.shard_map`` (every mesh axis manual) — the one
    form the train step and the serving engine's decode body use."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


# ------------------------------------------------------------------ axes
def client_axes(mesh: Mesh) -> tuple[str, ...]:
    """Mesh axes enumerating FSA aggregators (everything but the
    intra-model 'model' and 'pipe' axes)."""
    return tuple(a for a in mesh.axis_names if a not in ("model", "pipe"))


def client_count(mesh: Mesh) -> int:
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    return int(np.prod([sizes[a] for a in client_axes(mesh)]))


def _caxis(mesh: Mesh):
    ca = client_axes(mesh)
    return ca if len(ca) > 1 else ca[0]


def _model_size(mesh: Mesh) -> int:
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    return int(sizes.get("model", 1))


def _pipe_size(mesh: Mesh) -> int:
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    return int(sizes.get("pipe", 1))


# ----------------------------------------------------------- scatter dims
def _abstract_params(cfg):
    import functools
    from repro.models import transformer as tr
    return jax.eval_shape(functools.partial(tr.init_params, cfg=cfg),
                          jax.random.PRNGKey(0))


# --------------------------------------------------- tensor-parallel spec
# The per-leaf placement (TPSpec) and the derivation from param_spec role
# metadata live in the family-generic shard-plan subsystem; re-exported
# here because the mesh-side geometry below (local shapes, split/merge,
# composite store specs, wire layouts) is expressed in terms of them.
from repro.models.shard_plan import TPSpec, tp_specs  # noqa: E402,F401


def tp_local_shape(shape: tuple[int, ...], spec: TPSpec,
                   tp: int) -> tuple[int, ...]:
    """The per-model-position shape of a leaf under ``spec``."""
    if spec.dim < 0 or tp <= 1:
        return tuple(shape)
    shape = list(shape)
    shape[spec.dim] //= tp
    return tuple(shape)


def tp_split_leaf(x: jax.Array, spec: TPSpec, tp: int) -> jax.Array:
    """Materialize the per-position TP shards of one leaf: stacked
    ``(tp, *local_shape)``, shard i = model position i's slice (the same
    contiguous chunking ``P('model' @ dim)`` produces)."""
    if spec.dim < 0 or tp <= 1:
        return jnp.stack([x] * max(tp, 1))
    return jnp.stack(jnp.split(x, tp, axis=spec.dim))


def tp_merge_leaf(shards: jax.Array, spec: TPSpec) -> jax.Array:
    """Inverse of :func:`tp_split_leaf` (replicated leaves: shard 0)."""
    if spec.dim < 0:
        return shards[0]
    return jnp.concatenate(list(shards), axis=spec.dim)


# --------------------------------------------------------- pipeline dims
# The pipe axis slices the leading L-stacked layer dim of every block
# leaf into contiguous stages (models/shard_plan.PipelinePlan); non-block
# leaves (embed / lm_head / ln_f / proj_in) replicate over pipe — every
# stage embeds its own microbatch injection and the last stage computes
# the CE, so their grads are per-stage partials that psum over 'pipe'.
def pipe_dims(cfg, pp: int) -> Any:
    """Per-leaf pipe slice dim (0 for block leaves when the pipe axis is
    real, else -1), a pytree of ints matching the param tree."""
    from repro.models import transformer as tr
    spec = tr.param_spec(cfg)
    out: dict[str, Any] = {}
    for name in spec:
        if name == "blocks":
            out["blocks"] = {bn: (0 if pp > 1 else -1)
                             for bn in spec["blocks"]}
        else:
            out[name] = -1
    return out


def pipe_local_shape(shape: tuple[int, ...], pdim: int,
                     pp: int) -> tuple[int, ...]:
    """The per-pipe-stage shape of a (TP-local) leaf."""
    if pdim < 0 or pp <= 1:
        return tuple(shape)
    shape = list(shape)
    shape[pdim] //= pp
    return tuple(shape)


def pipe_grad_sync(grads: Any, pdims: Any, axis) -> Any:
    """After ``value_and_grad`` of the pipeline loss: block-leaf grads
    are stage-local (each stage owns its layer rows outright) and pass
    through; pipe-replicated leaves carry per-stage partial sums — psum
    them over the pipe axis."""
    return jax.tree.map(
        lambda g, pd: g if pd >= 0 else jax.lax.psum(g, axis),
        grads, pdims)


def tp_grad_sync(grads: Any, specs: Any, axis) -> Any:
    """Inside the manual region, after ``value_and_grad``: ``partial``
    leaves (replicated params consumed shard-locally) carry per-position
    partial sums — psum them over the model axis.  col/row/vocab grads
    are shard-local and replicate-kind grads already replicated, so both
    pass through untouched."""
    return jax.tree.map(
        lambda g, s: jax.lax.psum(g, axis) if s.kind == "partial" else g,
        grads, specs)


def scatter_dim_for(shape: tuple[int, ...], n_client: int) -> int:
    """Rightmost dim divisible by n_client, else -1 (replicate + psum)."""
    for d in range(len(shape) - 1, -1, -1):
        if shape[d] >= n_client and shape[d] % n_client == 0:
            return d
    return -1


def fsa_scatter_dims(cfg, mesh: Mesh) -> Any:
    """Per-leaf scatter dim for the FSA reduce-scatter / shard-local
    optimizer (pytree of ints matching the param tree).  Computed on the
    PIPE- and TP-LOCAL shape: inside the manual region every leaf is
    already this position's stage/model shard, and the client
    segmentation divides that."""
    n_client = client_count(mesh)
    tp = _model_size(mesh)
    pp = _pipe_size(mesh)
    params = _abstract_params(cfg)
    specs = tp_specs(cfg, tp)
    pdims = pipe_dims(cfg, pp)
    return jax.tree.map(
        lambda p, s, pd: scatter_dim_for(
            pipe_local_shape(tp_local_shape(p.shape, s, tp), pd, pp),
            n_client), params, specs, pdims)


# -------------------------------------------------------------- shardings
def _spec_with(dim: int, axes) -> P:
    if dim < 0:
        return P()
    parts: list = [None] * (dim + 1)
    parts[dim] = axes
    return P(*parts)


def _as_tuple(axes) -> tuple:
    return axes if isinstance(axes, tuple) else (axes,)


def composite_store_spec(tp_dim: int, fsa_dim: int, caxis,
                         pipe_dim: int = -1) -> P:
    """'store' PartitionSpec of one leaf: ``model`` at the TP dim times
    the client axes at the (TP-local) FSA scatter dim, times ``pipe`` at
    the stage slice dim (block leaves' L-stack).  When factors land on
    the same dim the intra-model axes are major — pipe, then model, then
    the client segmentation within."""
    if tp_dim < 0 and fsa_dim < 0 and pipe_dim < 0:
        return P()
    parts: list = [None] * (max(tp_dim, fsa_dim, pipe_dim) + 1)
    if pipe_dim >= 0:
        parts[pipe_dim] = ("pipe",)
    if tp_dim >= 0:
        parts[tp_dim] = (tuple(parts[tp_dim] or ()) + ("model",))
    if fsa_dim >= 0:
        parts[fsa_dim] = (tuple(parts[fsa_dim] or ()) + _as_tuple(caxis))
    return P(*[p[0] if isinstance(p, tuple) and len(p) == 1 else p
               for p in parts])


def store_specs(cfg, mesh: Mesh) -> Any:
    """Pytree of 'store'-layout PartitionSpecs (the composite pipe x
    model x client placement) matching the parameter tree."""
    caxis = _caxis(mesh)
    tp = _model_size(mesh)
    pp = _pipe_size(mesh)
    dims = fsa_scatter_dims(cfg, mesh)
    specs = tp_specs(cfg, tp)
    pdims = pipe_dims(cfg, pp)
    return jax.tree.map(
        lambda d, s, pd: composite_store_spec(s.dim, d, caxis, pd),
        dims, specs, pdims)


def dsc_store_spec(tp_leaf: TPSpec, caxis, pipe_dim: int = -1) -> P:
    """Layout of one client-stacked DSC-reference leaf, global shape
    ``(n_client, *full_leaf_shape)``: client axes at the stacking dim 0,
    ``model`` at the leaf's TP dim (and ``pipe`` at the stage dim)
    shifted by the stack."""
    n = max(tp_leaf.dim + 1, pipe_dim + 1, 0)
    parts: list = [caxis] + [None] * n
    if pipe_dim >= 0:
        parts[pipe_dim + 1] = "pipe"
    if tp_leaf.dim >= 0:
        prev = parts[tp_leaf.dim + 1]
        parts[tp_leaf.dim + 1] = ("model" if prev is None
                                  else (prev, "model"))
    return P(*parts)


def buffer_spec_tree(cfg, mesh: Mesh, fsa: bool = True) -> dict:
    """PartitionSpec tree of the FedBuff-style async aggregation buffer
    (``repro.core.pipeline.BufferState`` on the mesh): the staleness-
    weighted accumulator ``u`` mirrors the parameters' layout — each
    aggregator buffers its OWN disjoint segment under FSA (the composite
    store placement), the TP broadcast layout under the FedAvg baseline —
    and the cumulative weight / round counter are replicated scalars
    (every position folds the identical arrival mass)."""
    u = store_specs(cfg, mesh) if fsa else tp_param_in_specs(cfg, mesh)
    return {"u": u, "w": P(), "t": P()}


def shift_state_dtype(name: str):
    """Residency dtype of the DSC shift state (s_clients / s_agg) — the
    one knob ``TrainSettings.shift_dtype`` threads through the store
    layout.  bf16 halves the resident shift bytes (2 full model copies
    per client position otherwise); the fused wire kernels widen to f32
    on the fly inside VMEM, so only the HBM store narrows."""
    dt = jnp.dtype(name)
    if dt not in (jnp.dtype(jnp.float32), jnp.dtype(jnp.bfloat16),
                  jnp.dtype(jnp.float16)):
        raise ValueError(f"shift_dtype must be a float store dtype, "
                         f"got {name!r}")
    return dt


def tp_param_in_specs(cfg, mesh: Mesh) -> Any:
    """shard_map in_specs for the parameter broadcast: sharded over
    ``model`` at each leaf's TP dim and ``pipe`` at the block leaves'
    L-stack, replicated over the client axes (the boundary all-gather is
    the FSA broadcast, Algorithm 1 line 14)."""
    tp = _model_size(mesh)
    pp = _pipe_size(mesh)

    def one(s: TPSpec, pd: int) -> P:
        hi = max(s.dim, pd)
        if hi < 0:
            return P()
        parts: list = [None] * (hi + 1)
        if pd >= 0:
            parts[pd] = "pipe"
        if s.dim >= 0:
            parts[s.dim] = "model"
        return P(*parts)

    return jax.tree.map(one, tp_specs(cfg, tp), pipe_dims(cfg, pp))


def _use_spec(shape: tuple[int, ...], model: int) -> P:
    """Tensor-parallel placement hint: rightmost dim divisible by the
    model-axis size (GSPMD inserts whatever collectives remain)."""
    if model <= 1:
        return P()
    for d in range(len(shape) - 1, -1, -1):
        if shape[d] >= model and shape[d] % model == 0:
            return _spec_with(d, "model")
    return P()


def param_shardings(cfg, mesh: Mesh, mode: str = "store") -> Any:
    """NamedShardings for the parameter tree.

    * ``store`` — FSA x TP layout: each leaf split over ``model`` at its
      TP dim (per :func:`tp_specs`) and over the client axes at its
      TP-local scatter dim (aggregator a owns segment a); leaves with
      neither replicated.
    * ``use``   — serving/compute layout: replicated over client axes,
      tensor-parallel over 'model' where divisible (GSPMD hints).
    """
    params = _abstract_params(cfg)
    if mode == "store":
        return jax.tree.map(lambda s: NamedSharding(mesh, s),
                            store_specs(cfg, mesh),
                            is_leaf=lambda x: isinstance(x, P))
    if mode == "use":
        model = _model_size(mesh)
        return jax.tree.map(
            lambda p: NamedSharding(mesh, _use_spec(p.shape, model)), params)
    raise ValueError(f"unknown param layout {mode!r}")


def batch_shardings(cfg, mesh: Mesh, batch: Any) -> Any:
    """Batch inputs: leading (batch) dim over the client axes — each
    aggregator position trains its own client group's shard."""
    caxis = _caxis(mesh)

    def one(leaf):
        if getattr(leaf, "ndim", 0) == 0:
            return NamedSharding(mesh, P())
        return NamedSharding(mesh, P(caxis))

    return jax.tree.map(one, batch)


def cache_shardings(cfg, mesh: Mesh, cache: Any) -> Any:
    """Decode caches: (layer, batch, ...) leaves shard batch (dim 1) over
    the client axes."""
    caxis = _caxis(mesh)

    def one(leaf):
        if getattr(leaf, "ndim", 0) < 2:
            return NamedSharding(mesh, P())
        return NamedSharding(mesh, P(None, caxis))

    return jax.tree.map(one, cache)


def mirror_state_specs(params_abs: Any, param_leaf_specs: list,
                       state_abs: Any, default: P) -> Any:
    """Specs for an optimizer-state tree that mirrors the parameter tree
    leaf-wise (e.g. Adam mu/nu).  State leaves are matched positionally —
    leaf i of each params-shaped sub-tree gets param spec i — and
    anything that doesn't mirror a parameter (step counters, scalars)
    gets ``default``."""
    p_shapes = [tuple(p.shape) for p in jax.tree.leaves(params_abs)]
    n = len(p_shapes)
    leaves, treedef = jax.tree.flatten(state_abs)
    out, i = [], 0
    for leaf in leaves:
        shape = tuple(getattr(leaf, "shape", ()))
        if n and shape == p_shapes[i % n]:
            out.append(param_leaf_specs[i % n])
            i += 1
        else:
            out.append(default)
    return jax.tree.unflatten(treedef, out)


# ------------------------------------------------------ int8 wire layouts
@dataclasses.dataclass(frozen=True)
class WireLayout:
    """Per-leaf layout of the int8 wire payload for the FSA exchange.

    A leaf with scatter dim ``dim >= 0`` is split into ``n_client``
    contiguous segments along ``dim``; each segment is flattened, padded
    to a multiple of QBLOCK, and quantized per-256-block (int8 values +
    one f32 scale per block).  The (block, scale) pair is what crosses
    the mesh.  ``dim == -1`` leaves (no divisible dimension) stay on the
    un-quantized psum path in the runtime's ``grad_dtype``.
    """

    dim: int              # scatter dim (-1 = replicated, full psum)
    shard_elems: int      # un-padded elements per aggregator segment
    padded_elems: int     # rounded up to a QBLOCK multiple
    n_blocks: int         # scales per segment (= padded_elems // QBLOCK)

    @property
    def wire_bytes(self) -> int:
        """Bytes one client sends for ONE segment: int8 blocks + scales."""
        return self.padded_elems + 4 * self.n_blocks


def wire_layout_for(shape: tuple[int, ...], n_client: int) -> WireLayout:
    """Layout of one leaf's int8 wire payload (the geometry
    ``launch/train.py`` quantizes and ``all_to_all``s with)."""
    dim = scatter_dim_for(shape, n_client)
    if dim < 0:
        return WireLayout(-1, 0, 0, 0)
    m = int(np.prod(shape)) // n_client
    padded = -(-m // QBLOCK) * QBLOCK
    return WireLayout(dim, m, padded, padded // QBLOCK)


def int8_wire_layouts(cfg, mesh: Mesh) -> Any:
    """Pytree of :class:`WireLayout` matching the parameter tree (wire
    geometry of the PIPE/TP-LOCAL leaf each mesh position exchanges)."""
    n_client = client_count(mesh)
    tp = _model_size(mesh)
    pp = _pipe_size(mesh)
    params = _abstract_params(cfg)
    specs = tp_specs(cfg, tp)
    pdims = pipe_dims(cfg, pp)
    return jax.tree.map(
        lambda p, s, pd: wire_layout_for(
            pipe_local_shape(tp_local_shape(p.shape, s, tp), pd, pp),
            n_client), params, specs, pdims)


def mesh_wire_bytes(cfg, mesh: Mesh, *, int8: bool,
                    grad_bytes: int = 2) -> int:
    """Bytes ONE client (mesh position) puts on the client axes per round
    under the FSA exchange: the sum over leaves of every transmitted
    segment (n_client - 1 remote segments + its own, counted once each,
    matching the collective's logical payload).  With a model axis, each
    position exchanges only its TP-local shard, so this is per-position;
    model-axis psum traffic is accounted separately (``hlo_analysis``
    per-axis breakdown).  ``int8=False`` accounts the ``grad_dtype``
    path."""
    n_client = client_count(mesh)
    tp = _model_size(mesh)
    pp = _pipe_size(mesh)
    params = _abstract_params(cfg)
    specs = tp_specs(cfg, tp)
    pdims = pipe_dims(cfg, pp)
    total = 0
    for p, s, pd, lay in zip(
            jax.tree.leaves(params), jax.tree.leaves(specs),
            jax.tree.leaves(pdims),
            jax.tree.leaves(int8_wire_layouts(cfg, mesh),
                            is_leaf=lambda x: isinstance(x, WireLayout))):
        elems = int(np.prod(pipe_local_shape(
            tp_local_shape(p.shape, s, tp), pd, pp)))
        if int8 and lay.dim >= 0:
            total += n_client * lay.wire_bytes
        else:
            total += elems * grad_bytes
    return total


def param_bytes_per_device(cfg, mesh: Mesh) -> int:
    """Resident parameter bytes per device in the COMPUTE layout (every
    leaf at its pipe/TP-local shape, client-replicated) — the number the
    ≥26B acceptance bound (total / (tp * pp) within the replicated-leaf
    slack) is checked against in ``benchmarks/tp_snapshot``."""
    tp = _model_size(mesh)
    pp = _pipe_size(mesh)
    params = _abstract_params(cfg)
    specs = tp_specs(cfg, tp)
    pdims = pipe_dims(cfg, pp)
    total = 0
    for p, s, pd in zip(jax.tree.leaves(params), jax.tree.leaves(specs),
                        jax.tree.leaves(pdims)):
        shape = pipe_local_shape(tp_local_shape(p.shape, s, tp), pd, pp)
        total += int(np.prod(shape)) * jnp.dtype(p.dtype).itemsize
    return total


def split_shards(x: jax.Array, dim: int, n_client: int) -> jax.Array:
    """Reorganize a leaf into its FSA segments: ``(n_client, m)`` rows,
    row a = the flattened contiguous segment of ``dim`` that aggregator a
    owns (identical chunking to ``psum_scatter(..., tiled=True)`` and the
    'store' layout slices — the rows ARE the masks m_(a))."""
    pre, post = x.shape[:dim], x.shape[dim + 1:]
    size = x.shape[dim] // n_client
    x = x.reshape(*pre, n_client, size, *post)
    x = jnp.moveaxis(x, len(pre), 0)
    return x.reshape(n_client, -1)


def merge_shards(rows: jax.Array, dim: int, shape: tuple[int, ...],
                 n_client: int) -> jax.Array:
    """Inverse of :func:`split_shards` — reassemble ``(n_client, m)`` rows
    into the full leaf of ``shape``."""
    pre, post = shape[:dim], shape[dim + 1:]
    size = shape[dim] // n_client
    rows = rows.reshape(n_client, *pre, size, *post)
    rows = jnp.moveaxis(rows, 0, len(pre))
    return rows.reshape(shape)


def opt_state_shardings(cfg, mesh: Mesh, opt, params_abs: Any) -> Any:
    """Global-view NamedShardings for the optimizer state (mirrors the
    'store' parameter layout; scalars replicated)."""
    store = param_shardings(cfg, mesh, "store")
    state_abs = jax.eval_shape(opt.init, params_abs)
    specs = mirror_state_specs(
        params_abs,
        [s.spec for s in jax.tree.leaves(
            store, is_leaf=lambda x: isinstance(x, NamedSharding))],
        state_abs, P())
    return jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                        is_leaf=lambda x: isinstance(x, P))


# ---------------------------------------------------------------- serving
def paged_pool_shardings(cfg, mesh: Mesh) -> Any:
    """NamedShardings for the serving engine's paged KV pools
    ((L, N, KV, bs, hd) per layer): kv-heads shard over 'model' when
    they divide it — the same store/use machinery decision rule as
    ``_use_spec`` — else the pools replicate.  The block dim N stays
    unsharded: any request's table may point anywhere in the pool."""
    model = _model_size(mesh)
    if model > 1 and cfg.n_kv_heads % model == 0:
        spec = P(None, None, "model", None, None)
    else:
        spec = P()
    sh = NamedSharding(mesh, spec)
    return {"k": sh, "v": sh}


def serve_batch_shardings(mesh: Mesh) -> NamedSharding:
    """Sharding for the engine's per-step slot-batched inputs (tokens,
    context lens, block tables, sampling vectors): leading slot dim over
    the client axes — the serving twin of ``batch_shardings``."""
    return NamedSharding(mesh, P(_caxis(mesh)))
