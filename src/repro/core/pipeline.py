"""Composable FL round pipeline (the paper's Algorithm 1 as a stage graph).

ERIS composes orthogonally from four stages, and so does every baseline
the paper compares against (SoteriaFL frames private compressed FL the
same way):

    ClientStep      local stochastic gradients            (Alg. 1 line 3)
    CompressStage*  what leaves the client                (line 4: DSC /
                    error feedback / LDP noise / pruning / wire int8)
    AggregateStage  how shards meet                       (lines 5-13: FSA
                    sharded or algebraic / all-reduce / secure-agg /
                    shatter / failure-injected FSA)
    ServerStage     how the global model moves            (line 14 +
                    Sec. 5 'Benefits': fedavg / fedadam / fedyogi)

A method is a :class:`RoundPipeline` — a frozen declarative composition —
instead of a branch in an if/elif chain.  The same stage objects drive
the laptop simulator (``repro.core.fl``), the pure-functional scan engine
(``repro.core.eris``), and the distributed shard_map runtime
(``repro.launch.train`` calls ``CompressStage.apply_leaf`` per parameter
leaf), so simulator and production semantics cannot drift.

RNG discipline: every round splits its key into five role keys
(mask/comp/noise/fail/part) exactly like the original engine; each stage
declares which role it consumes, which keeps trajectories bit-compatible
with the pre-pipeline implementation (asserted in tests/test_pipeline.py).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.core import baselines as bl
from repro.core import dsc as dsc_lib
from repro.core import error_feedback as ef_lib
from repro.core import fsa as fsa_lib
from repro.core import masks as masks_lib
from repro.core import secure_agg as sa_lib
from repro.core import server_opt as so_lib
from repro.core.compressors import Compressor, Identity, RandP


# ================================================================== state
class BufferState(NamedTuple):
    """FedBuff-style aggregator buffer carried across rounds: the
    staleness-weighted update accumulator, its cumulative weight, and the
    absolute round counter driving the server-apply cadence."""
    u: jax.Array             # weighted update accumulator (n,)
    w: jax.Array             # cumulative arrival weight ()
    t: jax.Array             # rounds folded since start (int32, ())


def init_buffer(n: int) -> BufferState:
    return BufferState(jnp.zeros(n), jnp.zeros(()),
                       jnp.zeros((), jnp.int32))


class RoundState(NamedTuple):
    """Everything a round carries forward (a scan carry)."""
    x: jax.Array             # global model (n,)
    dsc: dsc_lib.DSCState    # DSC reference vectors (zeros when unused)
    ef: ef_lib.EFState       # error-feedback residuals (zeros when unused)
    server: Any              # server optimizer state
    buf: Any = None          # BufferState under buffered async aggregation


class RoundKeys(NamedTuple):
    """Per-round role keys (the engine's historical 5-way split, plus the
    two sub-keys SoteriaFL derives from ``comp`` and a dedicated wire
    key — ``comp1`` can collide with a client's compressor key since
    ``split(k, 2)[1] == split(k, K)[1]`` for K=2, so independent stages
    must not share it)."""
    mask: jax.Array
    comp: jax.Array
    noise: jax.Array
    fail: jax.Array
    part: jax.Array
    comp0: jax.Array         # split(comp)[0] — SoteriaFL pre-noise
    comp1: jax.Array         # split(comp)[1] — SoteriaFL compression
    wire: jax.Array          # wire-format stages (int8 quantization)


def split_round_keys(key: jax.Array) -> RoundKeys:
    k_mask, k_comp, k_noise, k_fail, k_part = jax.random.split(key, 5)
    c0, c1 = jax.random.split(k_comp)
    return RoundKeys(k_mask, k_comp, k_noise, k_fail, k_part, c0, c1,
                     jax.random.fold_in(k_comp, 0x3177))


def participation_weights(key: jax.Array, K: int,
                          fraction: float) -> Optional[jax.Array]:
    """Client-sampling weights: Bernoulli(fraction) per client with at
    least one participant forced (None when everyone participates)."""
    if fraction >= 1.0:
        return None
    # Distinct sub-keys: reusing ``key`` for both draws deterministically
    # coupled the forced index to the Bernoulli mask (same entropy).
    k_draw, k_force = jax.random.split(key)
    part = jax.random.bernoulli(k_draw, fraction, (K,))
    part = part.at[jax.random.randint(k_force, (), 0, K)].set(True)
    return part.astype(jnp.float32)


# ======================================================= async primitives
# Key salts: BufferedAggregate folds its role key with ARRIVAL_SALT and
# CohortSample with COHORT_SALT, so the arrival/cohort draws are
# decorrelated from every existing consumer of the same role key (the
# eris engine aliases fail/part to comp; FailureInjectedFSA splits fail
# directly) without changing any synchronous trajectory.
ARRIVAL_SALT = 0xA51C
COHORT_SALT = 0xC0C0
PAIRWISE_SALT = 0x6D5C   # PairwiseMask folds its role key with this, so
                         # composing it with LDPNoise (same "noise" role)
                         # draws decorrelated mask and noise streams


@dataclasses.dataclass(frozen=True)
class ArrivalModel:
    """Deterministic-keyed straggler/dropout arrivals (the FedBuff-style
    async client model): each cohort member arrives with staleness
    ``tau ~ U{0..delay_max}`` and survives dropout w.p. ``1 - dropout``;
    its update is weighted ``1/(1+tau)^alpha`` (Nguyen et al.'s FedBuff
    staleness discount) and a dropped client contributes NOTHING."""

    delay_max: int = 0
    dropout: float = 0.0
    alpha: float = 1.0

    @property
    def trivial(self) -> bool:
        """Statically no-op: zero staleness, zero dropout.  The trivial
        model draws no randomness and weights every arrival exactly 1.0,
        so buffered aggregation degenerates to the synchronous path
        bit-exactly (asserted in tests/test_fedbuff.py)."""
        return self.delay_max == 0 and self.dropout == 0.0

    def staleness_weight(self, tau: jax.Array) -> jax.Array:
        return (1.0 + tau.astype(jnp.float32)) ** (-self.alpha)

    def draw(self, key: jax.Array, K: int
             ) -> tuple[jax.Array, jax.Array, jax.Array]:
        """(tau, alive, weight) for a K-client cohort."""
        kd, ka = jax.random.split(key)
        tau = jax.random.randint(kd, (K,), 0, self.delay_max + 1)
        alive = jax.random.bernoulli(ka, 1.0 - self.dropout, (K,))
        omega = self.staleness_weight(tau) * alive.astype(jnp.float32)
        return tau, alive, omega


@dataclasses.dataclass(frozen=True)
class CohortSample:
    """Per-round cohort draw over a population: a keyed
    without-replacement sample of ``cohort`` client ids out of
    ``population``.  The draw is a pure function of the round's role key,
    so it is reproducible and identical across engines, and it traces —
    the scan engine compiles the whole population's cohort selection into
    the single fused T-round program."""

    population: int
    cohort: int
    key_role: str = "part"

    def __post_init__(self):
        if not 0 < self.cohort <= self.population:
            raise ValueError(
                f"cohort size {self.cohort} must be in 1..population "
                f"({self.population})")

    def draw(self, keys: RoundKeys) -> jax.Array:
        key = jax.random.fold_in(getattr(keys, self.key_role), COHORT_SALT)
        return jax.random.permutation(key, self.population)[:self.cohort]

    def gather(self, keys: RoundKeys, batches):
        """Select the cohort's rows from population-leading batch arrays
        (leading dim = population -> leading dim = cohort)."""
        idx = self.draw(keys)
        return idx, jax.tree.map(lambda b: jnp.take(b, idx, axis=0),
                                 batches)


# ======================================================== kernel plumbing
def _seed_of(key: jax.Array) -> jax.Array:
    return jax.random.bits(key, dtype=jnp.uint32)


# ================================================================= client
@dataclasses.dataclass(frozen=True)
class ClientStep:
    """Local update: one full-batch stochastic gradient per client,
    vmapped (Algorithm 1 line 3)."""

    def __call__(self, grad_fn: Callable, x: jax.Array, batches) -> jax.Array:
        return jax.vmap(lambda b: grad_fn(x, b))(batches)


# ============================================================== compress
@dataclasses.dataclass(frozen=True)
class CompressStage:
    """Base stage: identity (what FedAvg transmits)."""

    key_role: str = "comp"

    def _key(self, keys: RoundKeys) -> jax.Array:
        return getattr(keys, self.key_role)

    def apply(self, keys: RoundKeys, state: RoundState,
              v: jax.Array) -> tuple[jax.Array, RoundState]:
        return v, state


@dataclasses.dataclass(frozen=True)
class LDPNoise(CompressStage):
    """Per-client clip + Gaussian perturbation (LDP-FL / SoteriaFL's
    privacy mechanism)."""

    ldp: bl.LDPConfig = bl.LDPConfig()
    key_role: str = "noise"

    def apply(self, keys, state, v):
        return bl.ldp_perturb(self._key(keys), v, self.ldp), state


@dataclasses.dataclass(frozen=True)
class DSCCompress(CompressStage):
    """Distributed shifted compression, client side (Sec. 3.2.2):
    v_k = C(g_k - s_k);  s_k <- s_k + gamma v_k.

    ``impl='pallas'`` routes a RandP compressor through the fused
    ``kernels/dsc_update`` TPU kernel (interpret-mode on CPU): one kernel
    sweep instead of four HBM passes on the full model vector.

    ``impl='fused'`` goes one further for the int8-wire composition
    (``Int8RoundTrip(inner=RandP)``): the new ``kernels/dsc_quantize``
    kernel does mask-draw, shift-subtract, per-256-block stochastic int8
    AND the round-trip shift update in ONE VMEM pass — 2 reads + the
    int8 payload + 1 write, replacing the ~7-sweep two-kernel chain.
    The transmitted value is the dequantized wire payload, so the shift
    state tracks exactly what the aggregators receive.
    """

    compressor: Compressor = Identity()
    gamma: float = 0.0
    impl: str = "jnp"            # jnp | pallas | fused

    def compress(self, key: jax.Array, dsc: dsc_lib.DSCState,
                 grads: jax.Array) -> tuple[jax.Array, dsc_lib.DSCState]:
        if self.impl == "pallas":
            v, s_new = self._compress_pallas(key, dsc.s_clients, grads)
        elif self.impl == "fused":
            v, s_new = self._compress_fused(key, dsc.s_clients, grads)
        else:
            v, s_new = dsc_lib.client_compress(dsc, grads, self.compressor,
                                               self.gamma, key)
        return v, dsc._replace(s_clients=s_new)

    def _compress_fused(self, key, s_clients, grads):
        from repro.core.compressors import Int8RoundTrip
        from repro.kernels import dsc_quantize as dq_kernel
        from repro.kernels import quantize as q_kernel
        comp = self.compressor
        inner = comp.inner if isinstance(comp, Int8RoundTrip) else comp
        if not isinstance(inner, RandP):
            raise ValueError("fused DSC->int8 path needs a RandP (or "
                             "Int8RoundTrip(RandP)) compressor, got "
                             f"{comp.name!r}")
        K, n = grads.shape
        pad = (-n) % q_kernel.QBLOCK
        g = jnp.pad(grads.astype(jnp.float32),
                    ((0, 0), (0, pad))).reshape(-1)
        s = jnp.pad(s_clients, ((0, 0), (0, pad))).reshape(-1)
        # mirror Int8RoundTrip's key discipline: one subkey for the inner
        # RandP draw, one for the rounding draw
        k_in, k_q = jax.random.split(key)
        q, scale, s_new = dq_kernel.dsc_quantize(
            g, s, _seed_of(k_in), _seed_of(k_q), p=inner.p,
            gamma=self.gamma)
        # the simulator aggregates in f32, so reconstruct the wire value
        # (the distributed runtime ships q/scale and dequantizes receiver
        # side instead)
        v_hat = q_kernel.dequantize(q, scale)
        shape = (K, n + pad)
        return (v_hat.reshape(shape)[:, :n],
                s_new.reshape(shape)[:, :n])

    def _compress_pallas(self, key, s_clients, grads):
        from repro.kernels import dsc_update as dsc_kernel
        if not isinstance(self.compressor, RandP):
            raise ValueError("pallas DSC path needs a RandP compressor, "
                             f"got {self.compressor.name!r}")
        K, n = grads.shape
        pad = (-n) % dsc_kernel.LANES
        g = jnp.pad(grads, ((0, 0), (0, pad))).reshape(-1)
        s = jnp.pad(s_clients, ((0, 0), (0, pad))).reshape(-1)
        v, s_new = dsc_kernel.dsc_update(
            g, s, _seed_of(key), p=self.compressor.p, gamma=self.gamma)
        shape = (K, n + pad)
        return v.reshape(shape)[:, :n], s_new.reshape(shape)[:, :n]

    def apply(self, keys, state, v):
        v, dsc = self.compress(self._key(keys), state.dsc, v)
        return v, state._replace(dsc=dsc)

    def apply_leaf(self, key: jax.Array, g: jax.Array,
                   s: jax.Array) -> tuple[jax.Array, jax.Array]:
        """Single-client, single-leaf form for the distributed runtime
        (each shard_map position holds its own s_k leaf)."""
        v = self.compressor(key, g.astype(s.dtype) - s)
        return v, s + self.gamma * v


@dataclasses.dataclass(frozen=True)
class EFCompress(CompressStage):
    """EF21-style error feedback for BIASED compressors:
    v_k = C(g_k + e_k);  e_k <- g_k + e_k - v_k."""

    compressor: Compressor = Identity()

    def apply(self, keys, state, v):
        v, ef = ef_lib.client_compress(state.ef, v, self.compressor,
                                       self._key(keys))
        return v, state._replace(ef=ef)


@dataclasses.dataclass(frozen=True)
class PruneWithhold(CompressStage):
    """PriPrune-style defense: withhold (zero) the top-|g| fraction of
    coordinates of each client update before transmission."""

    rate: float = 0.1

    def apply(self, keys, state, v):
        return bl.prune_withhold(v, self.rate), state


@dataclasses.dataclass(frozen=True)
class Int8Wire(CompressStage):
    """Beyond-paper wire format: per-256-block stochastic int8
    quantize->dequantize round trip via the Pallas ``kernels/quantize``
    kernels (interpret-mode on CPU).  Unbiased, so it composes as an
    omega-compressor (Def. 3.1); payload ~1.03 B/coord vs 4 B f32."""

    key_role: str = "wire"

    def apply(self, keys, state, v):
        from repro.kernels import quantize as q_kernel
        K, n = v.shape
        pad = (-n) % q_kernel.QBLOCK
        flat = jnp.pad(v, ((0, 0), (0, pad))).reshape(-1)
        q, scale = q_kernel.quantize(flat, _seed_of(self._key(keys)))
        deq = q_kernel.dequantize(q, scale)
        return deq.reshape(K, n + pad)[:, :n], state


@dataclasses.dataclass(frozen=True)
class PairwiseMask(CompressStage):
    """Bonawitz pairwise masking as a WIRE stage — the composed-defense
    form: each client adds its row of the fixed-point pairwise mask grid
    before transmission, so every downstream aggregator view (FSA shards
    included) is masked, while the masks cancel exactly in the unweighted
    full-cohort sum.

    Composition caveat (enforced loudly, not silently): cancellation
    needs the plain full-cohort mean.  Partial participation, client
    dropout, or link failure leave unpaired masks of magnitude ``scale``
    in the aggregate — `rounds.scenarios` refuses those compositions and
    `SecureAggAggregate` raises on weighted aggregation."""

    scale: float = 100.0
    key_role: str = "noise"

    def apply(self, keys, state, v):
        key = jax.random.fold_in(self._key(keys), PAIRWISE_SALT)
        K, n = v.shape
        return v + sa_lib.pairwise_masks(key, K, n, self.scale), state


# ============================================================== aggregate
class AggregateResult(NamedTuple):
    update: jax.Array                    # aggregated pseudo-gradient (n,)
    state: RoundState
    views: Optional[jax.Array] = None    # adversary-view override


@dataclasses.dataclass(frozen=True)
class AggregateStage:
    """Base: exact weighted mean — FedAvg's all-reduce, equivalently FSA's
    algebraic form (Theorem B.1: all_reduce == all_gather . reduce_scatter
    over disjoint complete masks)."""

    use_weights: bool = True
    key_role: str = "comp"

    def _key(self, keys: RoundKeys) -> jax.Array:
        return getattr(keys, self.key_role)

    def _w(self, v: jax.Array, weights) -> jax.Array:
        K = v.shape[0]
        if weights is None or not self.use_weights:
            return jnp.full((K,), 1.0 / K)
        return weights / weights.sum()

    def mean(self, v: jax.Array, weights) -> jax.Array:
        return jnp.einsum("k,kn->n", self._w(v, weights), v)

    def apply(self, keys: RoundKeys, state: RoundState, v: jax.Array,
              weights) -> AggregateResult:
        return AggregateResult(self.mean(v, weights), state)


@dataclasses.dataclass(frozen=True)
class DSCAggregate(AggregateStage):
    """Aggregator-side shift compensation (Eq. 4):
    u = s_agg + mean_k v_k;  s_agg <- s_agg + gamma mean_k v_k."""

    gamma: float = 0.0

    def aggregate(self, dsc: dsc_lib.DSCState, v: jax.Array, weights
                  ) -> tuple[jax.Array, dsc_lib.DSCState]:
        u, s_agg = dsc_lib.aggregate(
            dsc, v, self.gamma, weights=weights if self.use_weights else None)
        return u, dsc._replace(s_agg=s_agg)

    def apply(self, keys, state, v, weights):
        u, dsc = self.aggregate(state.dsc, v, weights)
        return AggregateResult(u, state._replace(dsc=dsc))


@dataclasses.dataclass(frozen=True)
class FSASharded(AggregateStage):
    """Literal Algorithm 1 lines 5-13: materialize per-aggregator masked
    shards, aggregate each independently, reassemble.  Iterate-identical
    to the algebraic mean (Theorem B.1) but also exposes the
    honest-but-curious aggregator views — the privacy-eval path.

    ``fresh_masks`` draws a NEW random assignment every round (the
    paper's m^t notation) keyed on the round's ``mask`` role key, so the
    draw is reproducible and identical across engines.  ``use_dsc`` adds
    the aggregator-side shift compensation of Eq. 4 on the sharded mean
    (u = s_agg + mean; s_agg += gamma mean) — the composition the eris
    fresh-mask path runs.

    ``assign_override`` pins the coordinate->aggregator assignment to an
    explicit vector instead of a scheme — used by the privacy-audit
    harness to attack the simulator under the DISTRIBUTED runtime's
    per-leaf segment layout (``repro.privacy.views.mesh_flat_assignment``),
    so per-aggregator views are comparable across engines."""

    A: int = 4
    mask_scheme: str = "strided"
    keep_views: bool = True
    fresh_masks: bool = False        # re-draw random masks per round (m^t)
    use_dsc: bool = False
    gamma: float = 0.0
    key_role: str = "mask"
    assign_override: Optional[jax.Array] = None

    def assignment(self, keys: RoundKeys, n: int) -> jax.Array:
        if self.assign_override is not None:
            return self.assign_override
        if self.fresh_masks:
            return masks_lib.make_assignment(n, self.A, "random",
                                             key=self._key(keys))
        return masks_lib.make_assignment(n, self.A, self.mask_scheme)

    def apply(self, keys, state, v, weights):
        n = v.shape[1]
        assign = self.assignment(keys, n)
        out = fsa_lib.fsa_round_sharded(
            jnp.zeros(n), v, assign, self.A, 1.0,
            weights=weights if self.use_weights else None,
            keep_views=self.keep_views)
        mean_v = -out.x_new
        if self.use_dsc:
            dsc = state.dsc
            u = dsc.s_agg + mean_v
            state = state._replace(
                dsc=dsc._replace(s_agg=dsc.s_agg + self.gamma * mean_v))
        else:
            u = mean_v
        return AggregateResult(u, state, out.shard_views)


@dataclasses.dataclass(frozen=True)
class SecureAggAggregate(AggregateStage):
    """Bonawitz-style pairwise masking: the aggregate is the exact mean,
    the adversary view is the masked per-client updates.

    Pairwise masks cancel ONLY in the unweighted full-cohort mean — a
    weighted or partial sum (participation sampling, client dropout)
    leaves unpaired masks of magnitude ``scale`` in the aggregate, i.e.
    garbage.  The simplified protocol has no dropout recovery, so this
    stage fails loudly instead."""

    use_weights: bool = False

    def apply(self, keys, state, v, weights):
        if weights is not None:
            raise ValueError(
                "secure_agg cannot aggregate a weighted/partial cohort: "
                "pairwise masks cancel only in the unweighted full-cohort "
                "mean, and this simplified Bonawitz protocol has no "
                "dropout-recovery round (run with participation=1.0 / "
                "no client dropout, or pick a different defense)")
        masked = sa_lib.mask_updates(self._key(keys), v)
        return AggregateResult(masked.mean(0), state, masked)


@dataclasses.dataclass(frozen=True)
class ShatterAggregate(AggregateStage):
    """ShatterLite: coordinates in contiguous chunks, each chunk averaged
    over a random r-subset of clients (gossip-neighborhood approximation;
    intentionally deviates from the full mean)."""

    chunks: int = 8
    r: int = 4

    def apply(self, keys, state, v, weights):
        u = bl.shatter_update(self._key(keys), v, self.chunks, self.r)
        return AggregateResult(u, state)


@dataclasses.dataclass(frozen=True)
class FailureInjectedFSA(AggregateStage):
    """Appendix F.5: aggregator dropout + client->aggregator link failures
    on the transmitted shards; DSC shift compensation (when enabled) uses
    what the aggregators actually received.  ``keep_views`` materializes
    the (A, K, n) received shards (link-failed/dead entries zeroed) so the
    adversary-view audit can attack the failure-injected wire."""

    A: int = 4
    mask_scheme: str = "strided"
    agg_dropout: float = 0.0
    link_failure: float = 0.0
    use_dsc: bool = False
    gamma: float = 0.0
    key_role: str = "fail"
    keep_views: bool = False

    def apply(self, keys, state, v, weights):
        K, n = v.shape
        assign = masks_lib.make_assignment(n, self.A, self.mask_scheme)
        ka, kl = jax.random.split(self._key(keys))
        agg_alive = jax.random.bernoulli(ka, 1.0 - self.agg_dropout,
                                         (self.A,))
        link_alive = jax.random.bernoulli(kl, 1.0 - self.link_failure,
                                          (K, self.A))
        out = fsa_lib.fsa_round_with_failures(
            jnp.zeros(n), v, assign, self.A, 1.0, agg_alive, link_alive,
            keep_views=self.keep_views)
        if self.keep_views:
            x_acc, views = out.x_new, out.shard_views
        else:
            x_acc, views = out, None
        mean_v = -x_acc
        dsc = state.dsc
        if self.use_dsc:
            u = dsc.s_agg + mean_v
            dsc = dsc._replace(s_agg=dsc.s_agg + self.gamma * mean_v)
        else:
            u = mean_v
        return AggregateResult(u, state._replace(dsc=dsc), views)


@dataclasses.dataclass(frozen=True)
class BufferedAggregate(AggregateStage):
    """FedBuff-style buffered asynchronous aggregation around ANY inner
    aggregate stage: arrivals (drawn from ``arrival``) fold their
    staleness-weighted updates into a cross-round :class:`BufferState`;
    the server consumes the buffer only every ``cadence`` rounds and the
    update is zero in between.

    Per round the inner stage aggregates the arrived cohort with weights
    ``base_k * omega_k`` (``omega_k = alive_k / (1+tau_k)^alpha``), the
    buffer accumulates ``W_r * contrib`` with the round's arrival mass
    ``W_r = sum(base*omega)/sum(base)``, and an apply round emits
    ``buf.u / buf.w`` then resets.  With the TRIVIAL arrival model and
    ``cadence=1`` every step is algebraically `0 + 1.0*u`, `u / 1.0` —
    IEEE-exact identities — so the async path reproduces the synchronous
    inner stage bit-for-bit (the degenerate-case parity gate).

    The inner stage must consume weights (``use_weights=True``) so
    staleness discounts reach the mean; dropped clients are additionally
    hard-zeroed out of ``v`` (and the adversary views) so they can never
    contribute — a dropped client transmitted nothing."""

    inner: AggregateStage = AggregateStage()
    arrival: ArrivalModel = ArrivalModel()
    cadence: int = 1
    key_role: str = "fail"

    def __post_init__(self):
        if self.cadence < 1:
            raise ValueError(f"cadence must be >= 1, got {self.cadence}")
        if not self.inner.use_weights:
            raise ValueError(
                "BufferedAggregate needs an inner aggregate with "
                "use_weights=True; otherwise staleness/dropout weights "
                "would be silently ignored")

    def init_buffer(self, n: int) -> BufferState:
        return init_buffer(n)

    def apply(self, keys, state, v, weights):
        if state.buf is None:
            raise ValueError("BufferedAggregate needs RoundState.buf — "
                             "initialize via RoundPipeline.init_state "
                             "(or pipeline.init_buffer)")
        K = v.shape[0]
        if self.arrival.trivial:
            # statically synchronous: no draws, unit round weight — the
            # fold below is then bit-exact identity around the inner stage
            res = self.inner.apply(keys, state, v, weights)
            contrib, inner_state, views = res.update, res.state, res.views
            w_round = jnp.ones(())
        else:
            k_arr = jax.random.fold_in(self._key(keys), ARRIVAL_SALT)
            _, alive, omega = self.arrival.draw(k_arr, K)
            base = weights if (weights is not None and self.use_weights) \
                else jnp.ones((K,))
            w_eff = base * omega
            w_sum = w_eff.sum()
            # dropped clients transmitted nothing: hard-zero their rows
            # (and views) so no inner stage can leak or aggregate them
            v = v * alive[:, None].astype(v.dtype)
            safe_w = jnp.where(w_sum > 0, w_eff, jnp.ones((K,)))
            res = self.inner.apply(keys, state, v, safe_w)
            w_round = jnp.where(w_sum > 0, w_sum / base.sum(), 0.0)
            contrib = jnp.where(w_sum > 0, res.update, 0.0)
            inner_state, views = res.state, res.views
            if views is not None:
                # (A, K, n) aggregator views or (K, n) per-client views:
                # mask the cohort axis either way
                a = alive.astype(views.dtype)
                views = views * (a[None, :, None] if views.ndim == 3
                                 else a[:, None])
        buf = state.buf
        u_acc = buf.u + w_round * contrib
        w_acc = buf.w + w_round
        t_new = buf.t + 1
        do_apply = (t_new % self.cadence) == 0
        update = jnp.where(do_apply,
                           u_acc / jnp.maximum(w_acc, 1e-12), 0.0)
        buf_new = BufferState(u=jnp.where(do_apply, 0.0, u_acc),
                              w=jnp.where(do_apply, 0.0, w_acc),
                              t=t_new)
        return AggregateResult(update, inner_state._replace(buf=buf_new),
                               views)


# ================================================================= server
@dataclasses.dataclass(frozen=True)
class ServerStage:
    """Global model update from the aggregated pseudo-gradient.  Under FSA
    every aggregator runs the same coordinate-wise optimizer on its
    disjoint segment == the centralized update (Sec. 5 'Benefits')."""

    opt: str = "fedavg"          # fedavg | fedadam | fedyogi
    lr: float = 0.1

    def make(self) -> so_lib.ServerOpt:
        return so_lib.get_server_opt(self.opt, self.lr)

    def init(self, x0: jax.Array):
        return self.make().init(x0)

    def apply(self, state: RoundState, u: jax.Array) -> RoundState:
        delta, sstate = self.make().update(u, state.server)
        return state._replace(x=state.x + delta, server=sstate)


# =============================================================== pipeline
@dataclasses.dataclass(frozen=True)
class RoundPipeline:
    """One FL method, declaratively: client -> compress* -> aggregate ->
    server.  ``view`` names what an adversary observes: the transmitted
    per-client vectors, an aggregate-stage override, or nothing."""

    client: ClientStep = ClientStep()
    compress: tuple[CompressStage, ...] = ()
    aggregate: AggregateStage = AggregateStage()
    server: ServerStage = ServerStage()
    view: str = "none"           # none | transmitted
    cohort: Optional[CohortSample] = None   # population-scale cohort draw

    def init_state(self, x0: jax.Array, K: int) -> RoundState:
        n = x0.shape[0]
        buf = (self.aggregate.init_buffer(n)
               if isinstance(self.aggregate, BufferedAggregate) else None)
        return RoundState(x0, dsc_lib.init_state(K, n),
                          ef_lib.init_state(K, n), self.server.init(x0),
                          buf)

    def run_round(self, grad_fn: Callable, keys: RoundKeys,
                  state: RoundState, batches, weights=None
                  ) -> tuple[RoundState, Optional[jax.Array]]:
        """One round.  Returns (new_state, adversary_views).  With a
        ``cohort``, ``batches`` carries the WHOLE population on its
        leading axis and only the drawn cohort's rows are stepped."""
        if self.cohort is not None:
            _, batches = self.cohort.gather(keys, batches)
        grads = self.client(grad_fn, state.x, batches)
        v = grads
        for stage in self.compress:
            v, state = stage.apply(keys, state, v)
        agg = self.aggregate.apply(keys, state, v, weights)
        state = self.server.apply(agg.state, agg.update)
        views = agg.views if agg.views is not None else (
            v if self.view == "transmitted" else None)
        return state, views

    def scan_rounds(self, grad_fn: Callable, key: jax.Array,
                    state: RoundState, batches_stacked, weights=None,
                    participation: float = 1.0,
                    collect_views: bool = False):
        """All T rounds as ONE compiled program: ``jax.lax.scan`` over the
        leading (round) axis of ``batches_stacked``.  Key handling matches
        the per-round driver (split the carry key once per round), so the
        trajectory is identical to stepping — just without T dispatches
        and T retrace-sized XLA programs.  Returns (final_key, final_state,
        x_traj) with final_key advanced exactly as T step calls would.

        ``collect_views`` additionally stacks the per-round adversary
        views (the privacy-audit path: e.g. ``FSASharded.keep_views``
        shard views become one ``(T, A, K, n)`` array out of the single
        fused program)."""
        K = state.dsc.s_clients.shape[0]

        def body(carry, batches_t):
            k, st = carry
            k, sub = jax.random.split(k)
            keys = split_round_keys(sub)
            w = weights if weights is not None else \
                participation_weights(keys.part, K, participation)
            st, views = self.run_round(grad_fn, keys, st, batches_t, w)
            if collect_views:
                if views is None:
                    raise ValueError(
                        "collect_views: this pipeline exposes no adversary "
                        "view (view='none' and no aggregate override)")
                return (k, st), (st.x, views)
            return (k, st), st.x

        (key, state), out = jax.lax.scan(body, (key, state), batches_stacked)
        if collect_views:
            xs, views = out
            return key, state, xs, views
        return key, state, out
