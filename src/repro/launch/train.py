"""Distributed train step: FSA expressed as explicit TPU collectives.

The step is ONE fully-manual ``shard_map`` over ALL mesh axes — the
partial-auto mode (manual client axes + GSPMD model axis) tripped an
SPMD-partitioner check (``IsManualSubgroup``) on the 512-device configs,
so the lowering keeps nothing automatic:

  1. *FSA broadcast* — stored parameters are sharded over the client axes
     (each position = one aggregator's disjoint segment, Sec. 3.2.1); the
     shard_map in_spec requests them replicated, so XLA inserts the
     all-gather: x^t = sum_a m_(a) . x^t_(a)   (Algorithm 1 line 14).
  2. *Local update* — each client-axis position computes gradients on its
     own client group's batch shard.  The ``model`` axis runs
     manual-collective tensor parallelism under the family-generic shard
     plan (``models/shard_plan``): Megatron column/row pairs (QKV∘wo,
     gate/up∘down) wired through the ``tp_push``/``tp_pull`` conjugates,
     vocab-parallel embedding + unembed with the cross-entropy on
     vocab-sharded logits, expert-parallel MoE (expert-dim-sharded
     w_gate/w_up/w_down + token ``all_to_all`` dispatch/combine,
     replicated router with partial-grad psum), head-/channel-sharded
     recurrent mixers (mLSTM / hybrid mamba; the chunked scans run fully
     local), and optional sequence parallelism
     (``ModelConfig.seq_parallel``: the psum pairs become
     ``psum_scatter``/``all_gather`` conjugates so inter-region
     activations hold (B, S/tp, D)).  A config with NO shardable region
     falls back to the previous behavior: the model axis
     data-parallelizes the group batch when it divides, else replicates
     the group's computation.
  3. *DSC (optional)* — each client group shift-compresses its update
     v_k = C(g_k - s_k), s_k += gamma v_k, before transmission.
  4. *FSA aggregation* — the reduce-scatter stage.  Two wire formats:
       * ``grad_dtype`` (default bf16): ``psum_scatter`` over the client
         axes — each aggregator receives and reduces ONLY its disjoint
         shard (Theorem B.1: all_reduce == all_gather . reduce_scatter).
       * ``int8_wire``: each segment is quantized per-256-block
         (stochastic int8 + f32 scales, the Pallas ``kernels/quantize``
         pair), the int8 blocks + scales cross the mesh via ``all_to_all``
         (a sum cannot be performed in the quantized domain, so the
         reduce-scatter lowers to its scatter half; the reduction happens
         aggregator-side after dequantization).  With DSC, the shift
         references update from the quantized round trip — exactly the
         simulator's composed ``Int8RoundTrip`` compressor.
  5. *Shard-local optimizer* — aggregator a updates x_(a); optimizer state
     lives sharded (never materialized globally, ZeRO-style).

With ``fsa=False`` the baseline FedAvg schedule is emitted instead:
``pmean`` (all-reduce) of gradients + replicated optimizer.
"""
from __future__ import annotations

import dataclasses
import functools
import time
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import baselines as bl
from repro.core import secure_agg as sa_lib
from repro.core.compressors import RandP
from repro.core.eris import ROLE_SALTS
from repro.core.pipeline import (ARRIVAL_SALT, PAIRWISE_SALT, ArrivalModel,
                                 CohortSample, DSCCompress, split_round_keys)
from repro.core.settings import AsyncSettings, resolve_async
from repro.dist import sharding as sh
from repro.launch import shapes as shp
from repro.models import shard_plan as sp
from repro.models import transformer as tr
from repro.models.config import ModelConfig
from repro.optim import Optimizer, adam


# named scopes of the round's phases.  They cover ``fsa_body`` with no
# overlap, so every op of the compiled round carries one of them in its
# metadata and a device trace can be split by phase.
SCOPE_CLIENT = "eris.client"        # the client's gradient and its syncs
SCOPE_COMPRESS = "eris.compress"    # a leaf made ready for the wire
SCOPE_EXCHANGE = "eris.exchange"    # the client-axis collectives
SCOPE_AGGREGATE = "eris.aggregate"  # the receive side's reduce
SCOPE_UPDATE = "eris.update"        # the optimizer on the segment
# host spans of ``train``: one step span a round, and the loss read in it
SPAN_ROUND = "eris.round"
SPAN_READ_LOSS = "eris.read_loss"


@dataclasses.dataclass(frozen=True)
class TrainSettings:
    grad_dtype: str = "bfloat16"     # wire dtype for the un-quantized path
    int8_wire: bool = False          # int8 blocks + f32 scales on the mesh
    use_dsc: bool = False            # client-side shifted rand-p compression
    dsc_p: float = 0.1
    dsc_gamma: float = 0.5
    fused_wire: bool = True          # int8+DSC leaves through the one-pass
                                     # kernels/dsc_quantize kernel (mask,
                                     # shift-subtract, quantize, shift
                                     # update in a single VMEM sweep)
    shift_dtype: str = "float32"     # DSC shift-state residency (bf16
                                     # halves the resident s_k/s_agg bytes;
                                     # kernels widen to f32 on the fly)
    microbatches: int = 1            # 1F1B microbatch count when the mesh
                                     # has a real 'pipe' axis (the
                                     # wavefront scan runs m + p - 1 ticks;
                                     # bubble fraction (p-1)/(m+p-1))
    remat: bool = True
    fsa: bool = True                 # False => FedAvg all-reduce baseline
    capture_views: bool = False      # adversary-view tap: return, per
                                     # aggregator, the REAL observed wire
                                     # payload (dequantized int8 segments /
                                     # grad_dtype rows) as round output
    # ---- FedBuff-style buffered async aggregation (core.pipeline's
    # BufferedAggregate/ArrivalModel semantics on the mesh): arrivals fold
    # staleness-weighted updates into a per-segment buffer riding the
    # dsc-style state tree; params/optimizer apply every buffer_cadence
    # rounds.  Trivial arrivals + cadence 1 == the synchronous step
    # bit-exactly.
    # The flat fields are the deprecated spelling of
    # core.settings.AsyncSettings (shared with FLConfig); prefer
    # attaching one via ``async_``.  A knob set in BOTH places to
    # different values raises with the conflicting field named.
    async_buffer: bool = False
    buffer_cadence: int = 1
    staleness_alpha: float = 1.0
    delay_max: int = 0
    client_dropout: float = 0.0
    async_: Optional[AsyncSettings] = None
    # ---- composed-defense / failure knobs (the rounds.scenarios matrix
    # on the real mesh wire):
    ldp_eps: float = 0.0             # >0: per-client L2 clip + Gaussian
    ldp_delta: float = 1e-5          # noise BEFORE transmission (the
    ldp_clip: float = 1.0            # simulator's LDPNoise stage)
    secure_mask: bool = False        # Bonawitz pairwise wire masking
    agg_dropout: float = 0.0         # aggregator dropout (Appendix F.5)
    link_failure: float = 0.0        # client->aggregator link failure

    def async_settings(self) -> AsyncSettings:
        """The resolved async-runtime knobs (shared with FLConfig)."""
        return resolve_async("TrainSettings", self.async_, self)

    def arrival_model(self) -> ArrivalModel:
        return self.async_settings().arrival_model()

    def ldp_config(self) -> Optional[bl.LDPConfig]:
        if self.ldp_eps <= 0:
            return None
        return bl.LDPConfig(eps=self.ldp_eps, delta=self.ldp_delta,
                            clip=self.ldp_clip)


def dsc_stage(settings: TrainSettings) -> DSCCompress:
    """The simulator's DSC compression stage, shared verbatim by the
    distributed runtime (one DSC implementation, zero drift)."""
    return DSCCompress(compressor=RandP(p=settings.dsc_p),
                       gamma=settings.dsc_gamma)


def cohort_batch(batch, key: jax.Array, population: int, n_client: int):
    """Population-scale cohort selection for the distributed runtime: the
    SAME keyed :class:`CohortSample` draw the simulator/scan engines run
    inside their rounds, applied to population-leading batch arrays so
    the step's client-axis shard is the drawn cohort.  Returns
    ``(cohort_ids, gathered_batch)``."""
    cs = CohortSample(population=population, cohort=n_client)
    return cs.gather(split_round_keys(key), batch)


def dsc_spec_tree(cfg: ModelConfig, mesh: Mesh, settings: TrainSettings):
    """PartitionSpec tree of the DSC shift state — the ONE definition the
    shard_map specs, the jit in_shardings and ``init_dsc_state`` all
    derive from.  ``s_clients`` leaves are client-stacked on dim 0
    (each position holds its own s_k), TP-sharded over 'model' at the
    leaf's shifted TP dim; ``s_agg`` lives in the params' layout (store
    under FSA — each aggregator compensates its own segment — else the
    TP broadcast layout).  Without DSC: a replicated-scalar placeholder
    tree."""
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    tp_spec_tree = sh.tp_specs(cfg, int(sizes.get("model", 1)))
    pdim_tree = sh.pipe_dims(cfg, int(sizes.get("pipe", 1)))
    if not settings.use_dsc:
        specs = jax.tree.map(lambda s: P(), tp_spec_tree)
    else:
        ca = sh.client_axes(mesh)
        caxis = ca if len(ca) > 1 else ca[0]
        specs = {
            "s_clients": jax.tree.map(
                lambda s, pd: sh.dsc_store_spec(s, caxis, pd),
                tp_spec_tree, pdim_tree),
            "s_agg": (sh.store_specs(cfg, mesh) if settings.fsa
                      else sh.tp_param_in_specs(cfg, mesh)),
        }
    if settings.async_buffer:
        # the FedBuff buffer rides the same state tree: the accumulator
        # lives in the aggregators' segment layout (each position buffers
        # its own disjoint shard); weight/round counters are replicated
        return {"dsc": specs,
                "buffer": sh.buffer_spec_tree(cfg, mesh, fsa=settings.fsa)}
    return specs


def _client_size(mesh: Mesh) -> int:
    return sh.client_count(mesh)


def _wire_rows(x: jax.Array, n_client: int) -> jax.Array:
    """Each client's wire row as a lane-dense (rows, 128) tile stack.  A
    flat row per client pads to the chip's sublane tiling, and at
    embedding size its all_to_all and the cross-client reduce compile
    for minutes."""
    return x.reshape(n_client, -1, 128)


def _reduce_rows(rx: jax.Array, m: int, weights=None) -> jax.Array:
    """Aggregator-side reduce of the received (n_client, rows, 128) tiles
    to the segment's first ``m`` coordinates: the mean over clients, or
    the ``weights``-weighted sum."""
    my = (rx.mean(0) if weights is None
          else jnp.einsum("k,krl->rl", weights, rx))
    return my.reshape(-1)[:m]


def _int8_wire_exchange(v: jax.Array, dim: int, seed: jax.Array,
                        caxis, n_client: int,
                        need_round_trip: bool, omega=None, rx_w=None):
    """The int8 reduce-scatter stage for one leaf.

    Splits ``v`` into its n_client FSA segments, quantizes each segment
    per-256-block, sends the int8 blocks + f32 scales over the client
    axes (``all_to_all`` — segment a of every client lands on aggregator
    a), dequantizes aggregator-side and reduces.  Returns
    ``(my_segment_mean f32, v_hat, rx_rows)`` where ``v_hat`` is the
    local quantized round trip of the FULL leaf (what the aggregators
    actually received) for DSC shift updates, or None when not
    requested, and ``rx_rows`` is the (n_client, m) matrix of dequantized
    per-client segments this aggregator received — the literal
    honest-but-curious adversary view of this leaf (the adversary-view
    tap; dead code unless captured, XLA drops it).
    """
    from repro.kernels import quantize as q_kernel
    lay = sh.wire_layout_for(v.shape, n_client)      # the (block, scale)
    m, mp = lay.shard_elems, lay.padded_elems        # geometry on the wire
    with jax.named_scope(SCOPE_COMPRESS):
        rows = sh.split_shards(v.astype(jnp.float32), dim, n_client)
        rows = jnp.pad(rows, ((0, 0), (0, mp - m)))
        q, scale = q_kernel.quantize(rows.reshape(-1), seed)
        q = _wire_rows(q, n_client)
        scale = scale.reshape(n_client, lay.n_blocks)

        def deq(qq, ss):
            out = q_kernel.dequantize(qq.reshape(-1), ss.reshape(-1))
            return out.reshape(n_client, mp)[:, :m]

        v_hat = None
        if need_round_trip:
            v_hat = sh.merge_shards(deq(q, scale), dim, v.shape, n_client)
    # --- the wire: int8 blocks + f32 scales cross the client axes -------
    with jax.named_scope(SCOPE_EXCHANGE):
        q_rx = jax.lax.all_to_all(q, caxis, 0, 0, tiled=True)
        s_rx = jax.lax.all_to_all(scale, caxis, 0, 0, tiled=True)
    with jax.named_scope(SCOPE_AGGREGATE):
        rx = _wire_rows(q_kernel.dequantize(q_rx.reshape(-1),
                                            s_rx.reshape(-1)), n_client)
        rx_rows = rx.reshape(n_client, mp)[:, :m]     # (n_client, m) views
        if rx_w is not None:
            # failure-injected receive: rows weighted by live links,
            # renormalized by the live count (already folded into rx_w)
            my = _reduce_rows(rx, m, rx_w)
        elif omega is None:
            my = _reduce_rows(rx, m)                  # aggregator-side sum
        else:
            # staleness/dropout-weighted arrivals (async buffer): each
            # row is one client's segment, discounted by its arrival
            # weight
            my = _reduce_rows(rx, m, omega) / n_client
        shard_shape = list(v.shape)
        shard_shape[dim] //= n_client
        my = my.reshape(shard_shape)
    return my, v_hat, rx_rows


def _fused_wire_exchange(g: jax.Array, s: jax.Array, dim: int,
                         seed_mask: jax.Array, seed_round: jax.Array,
                         caxis, n_client: int, p: float, gamma: float,
                         rx_w=None):
    """The int8+DSC wire stage for one leaf through the one-pass
    ``kernels/dsc_quantize`` kernel.

    Splits gradient AND shift state into the n_client FSA segments, runs
    mask-draw / shift-subtract / per-256-block stochastic int8 / shift
    update in a single VMEM sweep per segment batch (2 reads + the int8
    payload + 1 write, vs the compressor->quantize->dequantize chain's ~7
    HBM sweeps of the leaf), then ships the int8 blocks + f32 scales over
    the client axes exactly like :func:`_int8_wire_exchange`.  The shift
    state tracks the dequantized wire value in-register (the simulator's
    ``Int8RoundTrip`` composition).  Returns
    ``(my_segment_mean f32, s_new, rx_rows)``.
    """
    from repro.kernels import dsc_quantize as dq_kernel
    from repro.kernels import quantize as q_kernel
    lay = sh.wire_layout_for(g.shape, n_client)
    m, mp = lay.shard_elems, lay.padded_elems
    with jax.named_scope(SCOPE_COMPRESS):
        g_rows = jnp.pad(
            sh.split_shards(g.astype(jnp.float32), dim, n_client),
            ((0, 0), (0, mp - m)))
        s_rows = jnp.pad(
            sh.split_shards(s.astype(jnp.float32), dim, n_client),
            ((0, 0), (0, mp - m)))
        q, scale, s_new_flat = dq_kernel.dsc_quantize(
            g_rows.reshape(-1), s_rows.reshape(-1), seed_mask, seed_round,
            p=p, gamma=gamma)
        q = _wire_rows(q, n_client)
        scale = scale.reshape(n_client, lay.n_blocks)
        s_new = sh.merge_shards(s_new_flat.reshape(n_client, mp)[:, :m],
                                dim, g.shape, n_client).astype(s.dtype)
    # --- the wire: int8 blocks + f32 scales cross the client axes -------
    with jax.named_scope(SCOPE_EXCHANGE):
        q_rx = jax.lax.all_to_all(q, caxis, 0, 0, tiled=True)
        s_rx = jax.lax.all_to_all(scale, caxis, 0, 0, tiled=True)
    with jax.named_scope(SCOPE_AGGREGATE):
        rx = _wire_rows(q_kernel.dequantize(q_rx.reshape(-1),
                                            s_rx.reshape(-1)), n_client)
        rx_rows = rx.reshape(n_client, mp)[:, :m]
        shard_shape = list(g.shape)
        shard_shape[dim] //= n_client
        my = _reduce_rows(rx, m, rx_w).reshape(shard_shape)
    return my, s_new, rx_rows


def make_train_step(cfg: ModelConfig, mesh: Mesh, opt: Optimizer,
                    settings: TrainSettings = TrainSettings()):
    """Returns (train_step, shardings dict)."""
    # GSPMD placement hints are meaningless (and illegal) inside the
    # fully-manual region — the model axis is manual like every other.
    if cfg.attn_batch_shard:
        cfg = dataclasses.replace(cfg, attn_batch_shard=False)
    if settings.async_buffer and settings.use_dsc:
        raise ValueError(
            "async_buffer does not compose with use_dsc: the Eq. 4 shift "
            "state tracks per-round aggregator receipts, which a cadence-"
            "delayed buffered apply breaks (int8_wire is the stateless "
            "wire format that does compose)")
    # one validation surface for the async knobs (shared with FLConfig):
    # raises naming the offending/conflicting field
    async_cfg = settings.async_settings()
    ca = sh.client_axes(mesh)
    caxis = ca if len(ca) > 1 else ca[0]
    n_client = _client_size(mesh)
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    model_size = int(sizes.get("model", 1))
    tp_plan = tr.tp_plan(cfg, model_size)
    use_tp = tp_plan.active
    tp_spec_tree = sh.tp_specs(cfg, model_size)
    pipe_size = int(sizes.get("pipe", 1))
    pipe_plan = sp.build_pipeline_plan(cfg, pipe_size, settings.microbatches)
    use_pipe = pipe_plan.active
    if pipe_size > 1 and not use_pipe:
        raise ValueError(
            f"mesh has a pipe axis of size {pipe_size} but no pipeline "
            f"plan applies to family={cfg.family!r} with "
            f"n_layers={cfg.n_layers} (layers must split into equal "
            f"contiguous stages) — drop the pipe axis or pick a "
            f"divisible stage count")
    if settings.capture_views and pipe_size > 1:
        raise ValueError(
            "capture_views does not compose with a pipe axis yet: the "
            "adversary-view tap concatenates wire segments over 'model' "
            "only, so stage-sliced block leaves would alias")
    # ---- scenario-pack validation (rounds.scenarios on the mesh) --------
    ldp = settings.ldp_config()
    failures = settings.agg_dropout > 0 or settings.link_failure > 0
    if (ldp is not None or settings.secure_mask or failures) \
            and not settings.fsa:
        raise ValueError(
            "ldp/secure_mask/agg_dropout/link_failure are FSA wire "
            "compositions; fsa=False has no per-aggregator wire to "
            "defend or fail")
    if (ldp is not None or settings.secure_mask) and (use_tp or use_pipe):
        raise ValueError(
            "ldp/secure_mask need each client's FULL local gradient "
            "(global-L2 clip / whole-leaf mask rows); run them on a "
            "client-axes-only mesh (model=pipe=1)")
    if settings.secure_mask:
        if settings.use_dsc or settings.int8_wire:
            raise ValueError(
                "secure_mask composes with the plain f32 wire only: DSC "
                "shifts and int8 quantization transform each client's "
                "payload independently, so the pairwise masks would no "
                "longer cancel in the cross-client sum")
        if settings.grad_dtype != "float32":
            raise ValueError(
                "secure_mask needs grad_dtype='float32': the fixed-point "
                "pairwise masks cancel exactly in f32 partial sums; a "
                "bf16 wire would round them into O(1) noise")
        if failures or async_cfg.arrival_model().dropout > 0:
            raise ValueError(
                "secure_mask cannot compose with failures/client dropout: "
                "pairwise masks cancel only in the full-cohort sum (the "
                "simplified protocol has no dropout-recovery round)")
    if failures and settings.async_buffer:
        raise ValueError(
            "agg_dropout/link_failure compose with the synchronous FSA "
            "step; the async buffered runtime models client dropout "
            "through its ArrivalModel instead")
    pipe_dim_tree = sh.pipe_dims(cfg, pipe_size)
    scatter_dims = sh.fsa_scatter_dims(cfg, mesh) if settings.fsa else None
    store = sh.param_shardings(cfg, mesh, "store" if settings.fsa else "use")

    def loss_fn(params, batch, tp=None, pipe=None):
        if pipe is not None:
            return tr.pipeline_loss_fn(params, cfg, batch, tp=tp, pipe=pipe)
        return tr.loss_fn(params, cfg, batch, tp=tp)

    # ---------------- the manual (per-mesh-position) body -----------------
    def fsa_body(aidx_arr, midx_arr, pidx_arr, params, opt_state, dsc_ref,
                 batch, key, *, model_split):
        # params arrive as this position's pipe-stage x TP shards,
        # replicated over the client axes (the all-gather / broadcast
        # happened at the shard_map boundary); batch is this client
        # group's shard, further split over the model axis only when
        # model_split (the non-TP fallback).  aidx_arr/midx_arr/pidx_arr
        # are this position's slices of arange(n_client)/arange(model)/
        # arange(pipe) — the aggregator id and model/pipe coordinates
        # (axis_index lowers to an unsupported PartitionId under manual
        # SPMD, so all three ride in as sharded inputs instead).
        aidx = aidx_arr[0]
        buf_ref = None
        if settings.async_buffer:
            buf_ref, dsc_ref = dsc_ref["buffer"], dsc_ref["dsc"]
        with jax.named_scope(SCOPE_AGGREGATE):
            # async arrivals: the SAME ArrivalModel draw the simulator's
            # BufferedAggregate runs, keyed on the replicated round key (no
            # aidx fold — every mesh position must agree on who arrived)
            arrival = async_cfg.arrival_model()
            alive = omega = w_round = None
            if settings.async_buffer and not arrival.trivial:
                _, alive, omega = arrival.draw(
                    jax.random.fold_in(key, ARRIVAL_SALT), n_client)
                w_round = omega.mean()
            # failure injection (Appendix F.5 on the mesh): draws keyed on
            # the replicated round key salted with the eris engine's fail
            # role — every mesh position must agree on which
            # aggregators/links died.  link_alive is [client k, aggregator
            # a]; a dead link zeroes k's contribution to a's segment, the
            # aggregator renormalizes by its live-receipt count, and a dead
            # aggregator freezes its segment.  Leaves with no FSA scatter
            # dim ride the healthy all-reduce — only the per-aggregator
            # wire can fail.
            agg_alive = link_alive = link_cnt = None
            if failures:
                ka, kl = jax.random.split(
                    jax.random.fold_in(key, ROLE_SALTS["fail"]))
                agg_alive = jax.random.bernoulli(
                    ka, 1.0 - settings.agg_dropout, (n_client,)
                    ).astype(jnp.float32)
                link_alive = jax.random.bernoulli(
                    kl, 1.0 - settings.link_failure, (n_client, n_client)
                    ).astype(jnp.float32)
                link_cnt = jnp.maximum(link_alive.sum(0), 1.0)
        with jax.named_scope(SCOPE_CLIENT):
            if use_tp or use_pipe:
                tp_rt = (tr.TPRuntime("model", model_size, midx_arr[0],
                                      tp_plan) if use_tp else None)
                pipe_rt = (sp.PipeRuntime("pipe", pipe_size, pidx_arr[0],
                                          pipe_plan) if use_pipe else None)
                loss_val, grads = jax.value_and_grad(loss_fn)(
                    params, batch, tp_rt, pipe_rt)
                # partial-kind leaves (replicated values consumed on local
                # shards, e.g. qk-norm scales) sum their grads over 'model'
                if use_tp:
                    grads = sh.tp_grad_sync(grads, tp_spec_tree, "model")
                if use_pipe:
                    # pipe-replicated leaves (embed/head/ln_f) accumulated
                    # only where their stage touched them — sum over 'pipe';
                    # stage-sliced block leaves are already complete locally
                    grads = sh.pipe_grad_sync(grads, pipe_dim_tree, "pipe")
                loss_val = jax.lax.pmean(loss_val, caxis)
            else:
                loss_val, grads = jax.value_and_grad(loss_fn)(params, batch)
                loss_axes = (*ca, "model") if model_split else caxis
                loss_val = jax.lax.pmean(loss_val, loss_axes)
                if model_split:
                    # model axis = intra-group data parallelism: the group's
                    # update is the mean over its model-axis micro-shards
                    grads = jax.tree.map(lambda g: jax.lax.pmean(g, "model"),
                                         grads)

        leaves, treedef = jax.tree.flatten(grads)
        if ldp is not None:
            with jax.named_scope(SCOPE_COMPRESS):
                # LDP stage (the simulator's LDPNoise, client-side on the
                # mesh): clip this position's FULL gradient to ldp.clip in
                # global L2, then add the calibrated Gaussian leaf-wise.
                # Noise keys fold the eris noise-role salt + leaf index +
                # aidx so every client draws independent noise.
                gn2_c = sum(jnp.sum(jnp.square(l.astype(jnp.float32)))
                            for l in leaves)
                clip_s = jnp.minimum(
                    1.0, ldp.clip / jnp.maximum(jnp.sqrt(gn2_c), 1e-12))
                sigma = bl.gaussian_sigma(ldp.eps, ldp.delta, ldp.clip)
                leaves = [
                    (l.astype(jnp.float32) * clip_s
                     + sigma * jax.random.normal(
                         jax.random.fold_in(jax.random.fold_in(
                             key, ROLE_SALTS["noise"] + i), aidx), l.shape)
                     ).astype(l.dtype)
                    for i, l in enumerate(leaves)]
        stage = dsc_stage(settings) if settings.use_dsc else None
        refs = (jax.tree.leaves(dsc_ref["s_clients"]) if settings.use_dsc
                else [None] * len(leaves))
        dims = (jax.tree.leaves(scatter_dims) if settings.fsa
                else [-1] * len(leaves))
        capture = settings.capture_views and settings.fsa

        # --- compress + FSA aggregation, leaf-wise ------------------------
        def wire_seed(i):
            k = jax.random.fold_in(jax.random.fold_in(key, 0x3177 + i), aidx)
            return jax.random.bits(k, dtype=jnp.uint32)

        def tap(rx):
            # adversary view of this leaf's received rows: a dropped
            # client transmitted nothing, so its captured row is zeroed
            if alive is not None:
                rx = rx * alive[:, None].astype(rx.dtype)
            return rx[None]

        def fail_tap(rx):
            # failure view: rows over dead links never arrived, and a
            # dead aggregator observed nothing at all
            if link_alive is not None:
                rx = rx * (link_alive[:, aidx] * agg_alive[aidx]
                           )[:, None].astype(rx.dtype)
            return rx

        # failure-weighted receive: aggregator aidx weights each received
        # row by its live links, renormalizes by the live count, and
        # zeroes out entirely when it died itself (replacing the uniform
        # 1/n_client mean of the healthy path)
        rx_w = None
        if link_alive is not None:
            with jax.named_scope(SCOPE_AGGREGATE):
                rx_w = link_alive[:, aidx] * agg_alive[aidx] / link_cnt[aidx]

        out_leaves, refs_new, views = [], [], {}
        for i, (g, s_stk, dim) in enumerate(zip(leaves, refs, dims)):
            int8 = settings.int8_wire and settings.fsa and dim >= 0
            if settings.secure_mask:
                # Bonawitz pairwise wire masking: this position adds ITS
                # row of the fixed-point mask grid (key replicated — row
                # identity comes from aidx), so every row an aggregator
                # receives is masked while the masks cancel EXACTLY in
                # the f32 cross-client sum; the aggregate differs from
                # the unmasked wire only by the f32 absorption error of
                # adding O(mask-scale) values to O(grad) values.
                with jax.named_scope(SCOPE_COMPRESS):
                    mk = jax.random.fold_in(
                        jax.random.fold_in(key, PAIRWISE_SALT), i)
                    mrow = sa_lib.pairwise_mask_row(mk, aidx, n_client,
                                                    int(g.size))
                    g = g + mrow.reshape(g.shape).astype(g.dtype)
            if stage is not None:
                # client-side shifted compression (Sec. 3.2.2) — the SAME
                # DSCCompress stage the simulator pipeline runs, leaf-wise.
                # s_clients leaves are client-stacked (n_client, *shape),
                # so each client-axis position holds its OWN s_k ((1,)).
                with jax.named_scope(SCOPE_COMPRESS):
                    k = jax.random.fold_in(jax.random.fold_in(key, i), aidx)
                    s = s_stk[0]
                if int8 and settings.fused_wire:
                    # one-pass kernel: mask-draw + shift-subtract +
                    # quantize + round-trip shift update in a single VMEM
                    # sweep of the leaf (the wire payload and Eq. 4
                    # semantics are identical to the chain below)
                    with jax.named_scope(SCOPE_COMPRESS):
                        seeds = (jax.random.bits(k, dtype=jnp.uint32),
                                 wire_seed(i))
                    agg, s_new, rx = _fused_wire_exchange(
                        g, s, dim, *seeds, caxis, n_client,
                        p=settings.dsc_p, gamma=settings.dsc_gamma,
                        rx_w=rx_w)
                    refs_new.append(s_new[None])
                    out_leaves.append(agg)
                    if capture:
                        with jax.named_scope(SCOPE_AGGREGATE):
                            views[str(i)] = fail_tap(rx)[None]
                    continue
                if int8:
                    # wire format INSIDE the shifted compressor: s_k must
                    # update with what the aggregators actually receive
                    # (the simulator's Int8RoundTrip(inner=RandP)).
                    with jax.named_scope(SCOPE_COMPRESS):
                        v = stage.compressor(k, g.astype(s.dtype) - s)
                        seed = wire_seed(i)
                    agg, v_hat, rx = _int8_wire_exchange(
                        v, dim, seed, caxis, n_client,
                        need_round_trip=True, rx_w=rx_w)
                    with jax.named_scope(SCOPE_COMPRESS):
                        refs_new.append((s + stage.gamma * v_hat
                                         ).astype(s.dtype)[None])
                    out_leaves.append(agg)
                    if capture:
                        with jax.named_scope(SCOPE_AGGREGATE):
                            views[str(i)] = fail_tap(rx)[None]
                    continue
                with jax.named_scope(SCOPE_COMPRESS):
                    v, s_new = stage.apply_leaf(k, g, s)
                    refs_new.append(s_new[None])
                    g = v.astype(g.dtype)
            if int8:
                with jax.named_scope(SCOPE_COMPRESS):
                    seed = wire_seed(i)
                agg, _, rx = _int8_wire_exchange(
                    g, dim, seed, caxis, n_client,
                    need_round_trip=False, omega=omega, rx_w=rx_w)
                out_leaves.append(agg)
                if capture:
                    with jax.named_scope(SCOPE_AGGREGATE):
                        views[str(i)] = tap(fail_tap(rx))
                continue
            # un-quantized path: reduce-scatter in grad_dtype
            if omega is not None and not (capture and dim >= 0):
                # arrival-weighted FSA without the view tap: discount the
                # own contribution BEFORE the reduce (each client-axis
                # position is one client; the collective sums the
                # weighted rows)
                with jax.named_scope(SCOPE_AGGREGATE):
                    g = g * omega[aidx].astype(g.dtype)
            with jax.named_scope(SCOPE_COMPRESS):
                g = g.astype(settings.grad_dtype)
            if settings.fsa and dim >= 0:
                if capture:
                    # the tap needs the PER-CLIENT segments, so the
                    # reduce-scatter lowers to its scatter half (exactly
                    # like the int8 wire) and the reduction runs
                    # aggregator-side — same result, exposed payload
                    with jax.named_scope(SCOPE_COMPRESS):
                        rows = sh.split_shards(g, dim, n_client)
                    with jax.named_scope(SCOPE_EXCHANGE):
                        rx = jax.lax.all_to_all(rows, caxis, 0, 0,
                                                tiled=True)
                    with jax.named_scope(SCOPE_AGGREGATE):
                        views[str(i)] = tap(fail_tap(rx)).astype(
                            jnp.float32)
                        shard_shape = list(g.shape)
                        shard_shape[dim] //= n_client
                        if rx_w is not None:
                            agg_row = jnp.einsum("k,km->m",
                                                 rx_w.astype(rx.dtype), rx)
                        elif omega is None:
                            agg_row = rx.mean(0)
                        else:
                            agg_row = jnp.einsum(
                                "k,km->m", omega.astype(rx.dtype), rx
                                ) / n_client
                        out_leaves.append(agg_row.reshape(shard_shape))
                    continue
                if link_alive is not None:
                    # failure-injected reduce-scatter: client aidx scales
                    # segment a by link_alive[aidx, a]/cnt_a BEFORE the
                    # collective, so the sum lands as the renormalized
                    # mean over live receipts; a dead aggregator's
                    # segment then freezes (zero update).
                    with jax.named_scope(SCOPE_AGGREGATE):
                        rows = sh.split_shards(g, dim, n_client)
                        w_l = (link_alive[aidx] / link_cnt).astype(g.dtype)
                        g = sh.merge_shards(rows * w_l[:, None], dim,
                                            g.shape, n_client)
                    with jax.named_scope(SCOPE_EXCHANGE):
                        g = jax.lax.psum_scatter(g, caxis,
                                                 scatter_dimension=dim,
                                                 tiled=True)
                    with jax.named_scope(SCOPE_AGGREGATE):
                        out_leaves.append(
                            g * agg_alive[aidx].astype(g.dtype))
                    continue
                with jax.named_scope(SCOPE_EXCHANGE):
                    g = jax.lax.psum_scatter(g, caxis, scatter_dimension=dim,
                                             tiled=True)
            else:
                with jax.named_scope(SCOPE_EXCHANGE):
                    g = jax.lax.psum(g, caxis)
            with jax.named_scope(SCOPE_AGGREGATE):
                out_leaves.append(g / n_client)

        grads = jax.tree.unflatten(treedef, out_leaves)
        with jax.named_scope(SCOPE_AGGREGATE):
            if settings.use_dsc:
                # Eq. 4 aggregator-side shift compensation, on this
                # aggregator's own segment (every term it needs is local):
                # u = s_agg + mean_k v_k ; s_agg <- s_agg + gamma mean_k v_k
                # — the DSCAggregate/FSASharded(use_dsc) composition the
                # simulator runs; without it the model update would miss
                # the mean-shift the clients subtracted.
                s_agg = dsc_ref["s_agg"]
                grads = jax.tree.map(lambda s, m: s + m.astype(s.dtype),
                                     s_agg, grads)
                s_agg = jax.tree.map(
                    lambda s, u: s + settings.dsc_gamma * (u - s),
                    s_agg, grads)
                dsc_ref = {"s_clients": jax.tree.unflatten(treedef,
                                                           refs_new),
                           "s_agg": s_agg}

            # --- FedBuff buffer fold + cadence gate (async runtime) ------
            do_apply = None
            if settings.async_buffer:
                # fold this round's arrival-weighted aggregate into the
                # buffer; the effective update is the buffer mean on apply
                # rounds and exactly zero in between.  Trivial arrivals +
                # cadence 1 make every step here an IEEE-exact identity
                # (0 + 1.0*u, u / 1.0), so the synchronous trajectory is
                # reproduced bit-for-bit.
                w_r = jnp.ones(()) if w_round is None else w_round
                u_acc = jax.tree.map(
                    lambda b, g: b + w_r * g.astype(b.dtype),
                    buf_ref["u"], grads)
                w_acc = buf_ref["w"] + w_r
                t_new = buf_ref["t"] + 1
                do_apply = (t_new % async_cfg.buffer_cadence) == 0
                grads = jax.tree.map(
                    lambda u: jnp.where(do_apply,
                                        u / jnp.maximum(w_acc, 1e-12), 0.0),
                    u_acc)
                buf_ref = {"u": jax.tree.map(
                               lambda u: jnp.where(do_apply, 0.0, u), u_acc),
                           "w": jnp.where(do_apply, 0.0, w_acc),
                           "t": t_new}

        # --- shard-local optimizer on this aggregator's segment ----------
        def my_shard(p, dim):
            if not settings.fsa or dim < 0:
                return p
            size = p.shape[dim] // n_client
            return jax.lax.dynamic_slice_in_dim(p, aidx * size, size,
                                                axis=dim)

        with jax.named_scope(SCOPE_UPDATE):
            params_shard = (jax.tree.map(my_shard, params, scatter_dims)
                            if settings.fsa else params)
            grads = jax.tree.map(lambda g, p: g.astype(p.dtype), grads,
                                 params_shard)
            delta, opt_state_new = opt.update(grads, opt_state, params_shard)
            # the update may be wider than the params (adam's f32 bias
            # correction): params keep their dtype across steps
            params_new = jax.tree.map(lambda p, d: (p + d).astype(p.dtype),
                                      params_shard, delta)
            if settings.async_buffer and async_cfg.buffer_cadence > 1:
                # the server consumes the buffer only on cadence rounds:
                # params and optimizer state hold still in between
                params_new = jax.tree.map(
                    lambda a, b: jnp.where(do_apply, a, b),
                    params_new, params_shard)
                opt_state_new = jax.tree.map(
                    lambda a, b: jnp.where(do_apply, a, b),
                    opt_state_new, opt_state)
            params_shard, opt_state = params_new, opt_state_new

            sq = [jnp.sum(jnp.square(g.astype(jnp.float32)))
                  for g in jax.tree.leaves(grads)]
            if use_tp or use_pipe:
                # TP-sharded leaves are disjoint over 'model' and block leaves
                # disjoint over 'pipe'; replicated ones must not be
                # double-counted by either axis sum — bucket each leaf by the
                # axes it is actually sharded over and psum per bucket
                tps = [s.dim >= 0 and use_tp
                       for s in jax.tree.leaves(tp_spec_tree)]
                pps = [pd >= 0 and use_pipe
                       for pd in jax.tree.leaves(pipe_dim_tree)]
                zero = jnp.zeros((), jnp.float32)
                buckets: dict = {}
                for x, t, pl in zip(sq, tps, pps):
                    axes = ((("model",) if t else ())
                            + (("pipe",) if pl else ()))
                    buckets[axes] = buckets.get(axes, zero) + x
                gn2 = zero
                for axes, tot in buckets.items():
                    gn2 = gn2 + (jax.lax.psum(tot, axes) if axes else tot)
            else:
                gn2 = sum(sq)
            gnorm = jax.lax.psum(gn2, caxis) ** 0.5 \
                if settings.fsa else jnp.sqrt(gn2)
        metrics = {"loss": loss_val.astype(jnp.float32), "grad_norm": gnorm}
        state_out = ({"dsc": dsc_ref, "buffer": buf_ref}
                     if settings.async_buffer else dsc_ref)
        if capture:
            return params_shard, opt_state, state_out, metrics, views
        return params_shard, opt_state, state_out, metrics

    # ------------------------- shard_map specs ---------------------------
    params_abs = jax.eval_shape(
        functools.partial(tr.init_params, cfg=cfg), jax.random.PRNGKey(0))
    # params enter TP-sharded over 'model', replicated over client axes
    # (the boundary all-gather is the FSA broadcast); they leave in the
    # composite store layout (model @ TP dim x client axes @ scatter dim)
    param_in_specs = sh.tp_param_in_specs(cfg, mesh)
    if settings.fsa:
        param_specs = sh.store_specs(cfg, mesh)
    else:
        param_specs = param_in_specs
    opt_abs_local = jax.eval_shape(opt.init, params_abs)
    # opt state mirrors params leaf-wise (positional; scalars replicated)
    opt_specs = sh.mirror_state_specs(
        params_abs,
        jax.tree.leaves(param_specs, is_leaf=lambda x: isinstance(x, P)),
        opt_abs_local, P())
    dsc_specs = dsc_spec_tree(cfg, mesh, settings)
    # adversary-view tap: each captured leaf is the (1, n_client, m)
    # per-client received-segment matrix of ONE aggregator; the leading
    # dim shards over the client axes (global (A, K, m)), the flattened
    # segment concatenates over 'model' (TP-local segments)
    if settings.capture_views and settings.fsa:
        view_spec = (P(caxis, None, "model")
                     if "model" in mesh.axis_names else P(caxis))
        view_specs = {str(i): view_spec
                      for i, d in enumerate(jax.tree.leaves(scatter_dims))
                      if d >= 0}
    else:
        view_specs = None

    def make_step():
        def step(params_stored, opt_state, dsc_ref, batch, key):
            # without an applicable TP plan the model axis falls back to
            # data-parallel over the group's batch when the global batch
            # divides all mesh positions, else replicated (see module
            # docstring)
            b0 = jax.tree.leaves(batch)[0].shape[0]
            model_split = (not use_tp and not use_pipe and model_size > 1
                           and b0 % (n_client * model_size) == 0)
            batch_spec = P((*ca, "model")) if model_split else P(caxis)
            pidx_spec = P("pipe") if "pipe" in mesh.axis_names else P()
            in_specs = (P(caxis),                                 # aidx
                        P("model"),                               # midx
                        pidx_spec,                                # pidx
                        param_in_specs,                           # broadcast
                        opt_specs, dsc_specs,
                        jax.tree.map(lambda _: batch_spec, batch),
                        P())
            out_specs = (param_specs, opt_specs, dsc_specs,
                         {"loss": P(), "grad_norm": P()})
            if view_specs is not None:
                out_specs = out_specs + (view_specs,)
            fn = sh.shard_map(
                functools.partial(fsa_body, model_split=model_split), mesh,
                in_specs=in_specs, out_specs=out_specs)
            return fn(jnp.arange(n_client, dtype=jnp.int32),
                      jnp.arange(model_size, dtype=jnp.int32),
                      jnp.arange(pipe_size, dtype=jnp.int32),
                      params_stored, opt_state, dsc_ref, batch, key)
        return step

    return make_step(), {"store": store,
                         "use": sh.param_shardings(cfg, mesh, "use")}


def abstract_train_state(cfg: ModelConfig, mesh: Mesh, opt: Optimizer,
                         settings: TrainSettings = TrainSettings()):
    """ShapeDtypeStructs of (params_stored, opt_state, dsc_ref).

    All three are GLOBAL (pre-shard_map) views with FULL logical shapes —
    the composite store sharding (model axis @ TP dim x client axes @
    scatter dim) and the shard_map specs do the slicing; optimizer/DSC
    state never materializes unsharded on a device (ZeRO-style).
    """
    n_client = _client_size(mesh)
    params = jax.eval_shape(
        functools.partial(tr.init_params, cfg=cfg), jax.random.PRNGKey(0))
    opt_state_global = jax.eval_shape(opt.init, params)
    if settings.use_dsc:
        sdt = sh.shift_state_dtype(settings.shift_dtype)
        dsc_global = {
            "s_clients": jax.tree.map(
                lambda p: jax.ShapeDtypeStruct((n_client, *p.shape), sdt),
                params),
            "s_agg": jax.tree.map(
                lambda p: jax.ShapeDtypeStruct(p.shape, sdt), params),
        }
    else:
        dsc_global = jax.tree.map(
            lambda p: jax.ShapeDtypeStruct((), jnp.float32), params)
    if settings.async_buffer:
        dsc_global = {"dsc": dsc_global, "buffer": {
            "u": jax.tree.map(
                lambda p: jax.ShapeDtypeStruct(p.shape, jnp.float32),
                params),
            "w": jax.ShapeDtypeStruct((), jnp.float32),
            "t": jax.ShapeDtypeStruct((), jnp.int32),
        }}
    return params, opt_state_global, dsc_global


def init_dsc_state(cfg: ModelConfig, mesh: Mesh,
                   settings: TrainSettings):
    """Materialize the (sharded) DSC shift state: zero client refs
    stacked over the client axes + a zero aggregator-side shift in the
    params' store layout (or a replicated-scalar tree when DSC is off —
    the step function's placeholder).  Layout = :func:`dsc_spec_tree`."""
    params_abs = jax.eval_shape(
        functools.partial(tr.init_params, cfg=cfg), jax.random.PRNGKey(0))
    if not settings.use_dsc:
        refs = jax.tree.map(lambda p: jnp.zeros((), jnp.float32),
                            params_abs)
        if not settings.async_buffer:
            return refs
    else:
        n_client = _client_size(mesh)
        sdt = sh.shift_state_dtype(settings.shift_dtype)
        refs = {
            "s_clients": jax.tree.map(
                lambda p: jnp.zeros((n_client, *p.shape), sdt), params_abs),
            "s_agg": jax.tree.map(
                lambda p: jnp.zeros(p.shape, sdt), params_abs),
        }
    if settings.async_buffer:
        refs = {"dsc": refs, "buffer": {
            "u": jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32),
                              params_abs),
            "w": jnp.zeros(()),
            "t": jnp.zeros((), jnp.int32),
        }}
    shardings = jax.tree.map(
        lambda s: NamedSharding(mesh, s),
        dsc_spec_tree(cfg, mesh, settings),
        is_leaf=lambda x: isinstance(x, P))
    return jax.device_put(refs, shardings)


def lower_train_step(cfg: ModelConfig, mesh: Mesh,
                     shape_name: str = "train_4k",
                     settings: TrainSettings = TrainSettings(),
                     opt: Optional[Optimizer] = None):
    """jit(...).lower() of the train step for (cfg, mesh, shape)."""
    opt = opt or adam(3e-4)
    step, shardings = make_train_step(cfg, mesh, opt, settings)
    params, opt_state, dsc_ref = abstract_train_state(cfg, mesh, opt,
                                                      settings)
    batch = shp.input_specs(cfg, shape_name)
    batch_sh = sh.batch_shardings(cfg, mesh, batch)
    store = shardings["store"]
    opt_sh = sh.opt_state_shardings(cfg, mesh, opt, params)
    rep = NamedSharding(mesh, P())
    dsc_sh = jax.tree.map(lambda s: NamedSharding(mesh, s),
                          dsc_spec_tree(cfg, mesh, settings),
                          is_leaf=lambda x: isinstance(x, P))
    key = jax.ShapeDtypeStruct((2,), jnp.uint32)
    jitted = jax.jit(
        step,
        in_shardings=(store, opt_sh, dsc_sh, batch_sh, rep),
        donate_argnums=(0, 1, 2))
    with mesh:
        return jitted.lower(params, opt_state, dsc_ref, batch, key)


@dataclasses.dataclass
class TrainRun:
    """What :func:`train` returns: the final stored params, optimizer and
    DSC state, the loss of every step, and the compiled step (its text
    shows which kernels the compiler kept).  ``compile_s`` is host wall
    clock in seconds."""
    params: Any
    opt_state: Any
    dsc_ref: Any
    losses: list[float]
    compiled: Any
    compile_s: float


def train(cfg: ModelConfig, mesh: Mesh, opt: Optimizer,
          settings: TrainSettings, batch, steps: int,
          key: Optional[jax.Array] = None, log=None,
          profile_dir: Optional[str] = None) -> TrainRun:
    """Distributed FSA training on ``mesh``: parameters initialized from
    ``key`` and placed in the store layout, the step compiled once (with
    the state donated), then ``steps`` steps on the one ``batch``.
    ``log``, if given, receives a line per step.  Each step is a
    ``SPAN_ROUND`` step span on the profiler's host clock and its loss
    read a ``SPAN_READ_LOSS`` span; with ``profile_dir`` the steps after
    the first are traced by ``jax.profiler`` into that directory."""
    key = jax.random.PRNGKey(0) if key is None else key
    step, shardings = make_train_step(cfg, mesh, opt, settings)
    with mesh:
        params = jax.device_put(tr.init_params(key, cfg), shardings["store"])
        opt_state = opt.init(params)
        dsc_ref = init_dsc_state(cfg, mesh, settings)
        t0 = time.perf_counter()
        compiled = jax.jit(step, donate_argnums=(0, 1, 2)).lower(
            params, opt_state, dsc_ref, batch, key).compile()
        compile_s = time.perf_counter() - t0
        losses, tracing = [], False
        try:
            for i in range(steps):
                if i == 1 and profile_dir is not None:
                    jax.profiler.start_trace(profile_dir)
                    tracing = True
                t0 = time.perf_counter()
                with jax.profiler.StepTraceAnnotation(SPAN_ROUND,
                                                      step_num=i):
                    params, opt_state, dsc_ref, m = compiled(
                        params, opt_state, dsc_ref, batch,
                        jax.random.PRNGKey(i))
                    with jax.profiler.TraceAnnotation(SPAN_READ_LOSS):
                        losses.append(float(m["loss"]))
                if log is not None:
                    log(f"step {i:3d} loss={losses[-1]:.4f} "
                        f"({time.perf_counter() - t0:.2f}s)")
        finally:
            if tracing:
                jax.profiler.stop_trace()
    return TrainRun(params, opt_state, dsc_ref, losses, compiled,
                    compile_s)


def main():  # pragma: no cover - thin CLI over train()
    """CLI: distributed FSA training on the host devices.

        XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        PYTHONPATH=src python -m repro.launch.train --arch qwen2-0.5b \
            --smoke --steps 20
    """
    import argparse
    from repro.configs import get_config
    from repro.data import lm_token_batches
    from repro.launch.compile_cache import enable_compile_cache
    from repro.launch.mesh import make_host_mesh
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-0.5b")
    ap.add_argument("--smoke", action="store_true",
                    help="reduced same-family variant (CPU-sized)")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=1e-2)
    ap.add_argument("--dsc", action="store_true")
    ap.add_argument("--int8-wire", action="store_true")
    ap.add_argument("--data-axis", type=int, default=None)
    ap.add_argument("--model-axis", type=int, default=1)
    ap.add_argument("--pp", type=int, default=1,
                    help="pipe axis size (contiguous layer stages)")
    ap.add_argument("--microbatches", type=int, default=1,
                    help="1F1B microbatch count (must divide --batch)")
    ap.add_argument("--profile", default=None, metavar="DIR",
                    help="trace the steps after the first with "
                         "jax.profiler into DIR")
    ap.add_argument("--save", default=None, metavar="DIR",
                    help="write the final params as a sharded checkpoint "
                         "directory (the ServeEngine.from_checkpoint "
                         "handoff format)")
    args = ap.parse_args()
    enable_compile_cache()
    cfg = get_config(args.arch)
    if args.smoke:
        cfg = cfg.smoke()
    mesh = make_host_mesh(data=args.data_axis, model=args.model_axis,
                          pipe=args.pp)
    settings = TrainSettings(use_dsc=args.dsc, grad_dtype="float32",
                             int8_wire=args.int8_wire,
                             microbatches=args.microbatches)
    key = jax.random.PRNGKey(0)
    toks = lm_token_batches(key, 1, args.batch, args.seq, cfg.vocab)[0]
    run = train(cfg, mesh, adam(args.lr), settings, {"tokens": toks},
                args.steps, key, log=lambda line: print(line, flush=True),
                profile_dir=args.profile)
    print(f"compiled in {run.compile_s:.1f}s", flush=True)
    if args.save:
        from repro.checkpoint import msgpack_ckpt as ck
        ck.save_sharded(args.save, run.params)
        print(f"saved sharded checkpoint -> {args.save}", flush=True)


if __name__ == "__main__":
    main()
