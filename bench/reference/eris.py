"""Plain reference of the first rounds of ERIS training, and its control.

One round, as the paper and the traffic file state it, for K clients that
each hold ``rows_per_client`` rows of the round's batch:

    g_k   = grad of client k's mean loss              (float32, ``highest``)
    v_k   = mask_k * (g_k - s_k) / p                  (DSC, mask ~ Bernoulli(p))
    w_k   = int8 round trip of v_k, per 256-value block with scale
            max|block| / 127 and stochastic rounding  (the int8 wire)
    s_k  += gamma * w_k
    u     = mean_k (s_k_before + w_k)                 (FSA reduce + Eq. 4)
    x    <- Adam(u)

Without DSC, w_k is g_k (int8 round trip or the wire dtype) and u is their
mean.  The aggregator shift of Eq. 4, s_agg = gamma * sum_t mean_k w_k, is
the mean of the client shifts, so it is not stored apart.  All arithmetic
is float32; the weights and Adam's moments are stored between rounds in
the configuration's dtype, the shifts in ``shift_dtype``, as the round
stores them.  The masks and roundings are drawn from this module's own
keys, so they differ from the program's draws: what is compared are
quantities that do not depend on which coordinates were drawn.

Client k runs on device k mod the devices given, so the four-client round
fits four chips; the mean, the shifts' mean and Adam run on device 0.
The row blocks of one client run one row at a time, so the (S, vocab)
logits of one row are the largest activation.
"""
from __future__ import annotations

import dataclasses
import functools
import json
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from bench import tokens as tok
from bench.reference import model

HIGHEST = jax.lax.Precision.HIGHEST
QBLOCK = 256
FP8 = jnp.float8_e4m3fn
FP8_MAX = 448.0


# ------------------------------------------------------------ precisions
def _f32_matmul(a, b):
    return jnp.matmul(a, b, precision=HIGHEST)


def _f32_einsum(spec, a, b):
    return jnp.einsum(spec, a, b, precision=HIGHEST)


def _fp8(x):
    """x rounded to float8 e4m3 under a per-tensor scale (amax -> 448)."""
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / FP8_MAX
    return (x / scale).astype(FP8).astype(jnp.float32) * scale


def _int8(x):
    """x rounded to symmetric int8 under a per-tensor scale (amax -> 127)."""
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 127.0
    return jnp.clip(jnp.round(x / scale), -127, 127) * scale


def _lowered(rounding):
    """(matmul, einsum) whose every product, forward and both backward
    ones, takes its operands rounded by ``rounding``: operands on the way
    in, the cotangent on the way back."""
    @jax.custom_vjp
    def rin(x):
        return rounding(x)
    rin.defvjp(lambda x: (rounding(x), None), lambda _, ct: (ct,))

    @jax.custom_vjp
    def rct(x):
        return x
    rct.defvjp(lambda x: (x, None), lambda _, ct: (rounding(ct),))

    def matmul(a, b):
        return rct(jnp.matmul(rin(a), rin(b), precision=HIGHEST))

    def einsum(spec, a, b):
        return rct(jnp.einsum(spec, rin(a), rin(b), precision=HIGHEST))
    return matmul, einsum


PRECISIONS = {"float32": (_f32_matmul, _f32_einsum),
              "float8": _lowered(_fp8),
              "int8": _lowered(_int8)}


# -------------------------------------------------------------- the wire
def int8_round_trip(key, x):
    """Per-256-block stochastic int8 quantize + dequantize of flat x."""
    n = x.size
    xb = jnp.pad(x.reshape(-1), (0, -n % QBLOCK)).reshape(-1, QBLOCK)
    scale = jnp.max(jnp.abs(xb), axis=1, keepdims=True) / 127.0
    y = xb / jnp.where(scale > 0, scale, 1.0)
    low = jnp.floor(y)
    q = low + (jax.random.uniform(key, y.shape) < y - low)
    return (jnp.clip(q, -127, 127) * scale).reshape(-1)[:n].reshape(x.shape)


@dataclasses.dataclass
class Readings:
    """What the comparison reads of a run's first rounds: the loss of each
    round, and per leaf the norms of the reference's first mean gradient
    (``grad``), of the first aggregated update the optimizer got
    (``update``), of the weights' change over the rounds (``change``) and,
    with DSC, of the shift state after them (``shift``, see
    :func:`shift_readings`)."""
    losses: list
    update: dict
    change: dict
    grad: Optional[dict] = None
    shift: Optional[dict] = None


def shift_readings(clients: dict, agg: dict) -> dict:
    """{"<leaf>/clients": norm of every client's shift of the leaf taken
    together, "<leaf>/agg": norm of the aggregator's shift}."""
    return {**{f"{k}/clients": n for k, n in clients.items()},
            **{f"{k}/agg": n for k, n in agg.items()}}


def leaf_names(tree) -> list:
    return [jax.tree_util.keystr(p)
            for p, _ in jax.tree_util.tree_flatten_with_path(tree)[0]]


@jax.jit
def _norms(tree):
    return [jnp.linalg.norm(x.astype(jnp.float32)) for x in jax.tree.leaves(tree)]


def leaf_norms(tree) -> dict:
    """{leaf name: float32 L2 norm} on the host."""
    return dict(zip(leaf_names(tree), map(float, jax.device_get(_norms(tree)))))


def run(conf: dict, traffic: dict, seed_key: jax.Array, *, steps: int = 3,
        precision: str = "float32", stream: int = 1,
        fault: Optional[str] = None, devices=None,
        log=lambda s: None) -> Readings:
    """The reference's first ``steps`` rounds from the weights and batches
    that ``seed_key`` gives (the benchmark's own ``init`` and token pool).
    ``stream`` picks this run's mask and rounding draws; ``fault`` plants
    one of the faults the comparison must catch: ``frozen`` (the round
    leaves its state, shifts included, unchanged), ``stale_shift`` (the
    shifts are never updated), ``half_batch`` (each client's mean over the
    first half of its rows) or ``no_exchange`` (segment a of the update
    taken from client a alone)."""
    devices = devices or jax.devices()[:1]
    dev0 = devices[0]
    wire, opt = traffic["wire"], traffic["optimizer"]
    K, rows, S = traffic["clients"], traffic["rows_per_client"], traffic["seq_len"]
    k_w, k_d = jax.random.split(seed_key)
    matmul, einsum = PRECISIONS[precision]
    net = model(conf)
    params = jax.jit(functools.partial(net.init, conf=conf),
                     out_shardings=SingleDeviceSharding(dev0))(k_w)
    log("reference weights made")
    p_dtype = jax.tree.leaves(params)[0].dtype
    s_dtype = jnp.dtype(wire.get("shift_dtype", "float32"))
    w_dtype = jnp.dtype(wire.get("grad_dtype", "bfloat16"))
    batches = jax.device_get(jax.jit(functools.partial(
        tok.pool, traffic=traffic, vocab=net.dims(conf)["V"],
        rounds=traffic["pool_rounds"]))(k_d))[:steps]
    use_rows = rows // 2 if fault == "half_batch" else rows

    @jax.jit
    def row_grad(p, row):
        pf = jax.tree.map(lambda x: x.astype(jnp.float32), p)
        return jax.value_and_grad(net.loss)(pf, row[None], conf, matmul,
                                              einsum)

    @functools.partial(jax.jit, donate_argnums=(0,))
    def accumulate(acc, g):
        return jax.tree.map(jnp.add, acc, g)

    dsc = bool(wire.get("use_dsc"))

    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def client_wire(g, s, key):
        """One client's DSC and wire, leaf by leaf: returns its term of
        the aggregated update, (s_before + w) with DSC and w without, and
        the client's new shift."""
        leaves, treedef = jax.tree.flatten(g)
        s_l = jax.tree.leaves(s) if dsc else [None] * len(leaves)
        out_u, out_s = [], []
        for i, (gi, si) in enumerate(zip(leaves, s_l)):
            km, kq = jax.random.split(jax.random.fold_in(key, i))
            gi = gi / use_rows
            if dsc:
                s0 = si.astype(jnp.float32)
                mask = jax.random.uniform(km, gi.shape) < wire["dsc_p"]
                gi = jnp.where(mask, (gi - s0) / wire["dsc_p"], 0.0)
            if wire.get("int8_wire"):
                wi = int8_round_trip(kq, gi)
            else:
                wi = gi.astype(w_dtype).astype(jnp.float32)
            if dsc:
                out_s.append((s0 + wire["dsc_gamma"] * wi).astype(s_dtype))
                wi = s0 + wi
            out_u.append(wi)
        return (jax.tree.unflatten(treedef, out_u),
                jax.tree.unflatten(treedef, out_s) if dsc else s)

    @functools.partial(jax.jit, static_argnames=("k",), donate_argnums=(0, 1))
    def share(u, w, k):
        """Add client k's term to the update ``u`` (None before the first
        client): w / K, or with the exchange left out, segment k of every
        leaf from client k alone."""
        def one(a, b):
            if fault != "no_exchange":
                return a + b / K
            flat = b.reshape(-1)
            idx = jnp.arange(flat.size)
            own = (idx >= flat.size * k // K) & (idx < flat.size * (k + 1) // K)
            return a + jnp.where(own, flat, 0.0).reshape(b.shape)
        leaves, treedef = jax.tree.flatten(w)
        acc = jax.tree.leaves(u) if u is not None else [0.0] * len(leaves)
        return jax.tree.unflatten(treedef, [one(a, b)
                                            for a, b in zip(acc, leaves)])

    @functools.partial(jax.jit, donate_argnums=(0, 1, 2, 3))
    def adam(p, m, v, u, t):
        b1, b2, lr, eps = opt["b1"], opt["b2"], opt["lr"], opt["eps"]
        m = jax.tree.map(lambda a, g: b1 * a.astype(jnp.float32) + (1 - b1) * g,
                         m, u)
        v = jax.tree.map(lambda a, g: b2 * a.astype(jnp.float32)
                         + (1 - b2) * g * g, v, u)
        bc1, bc2 = 1 - b1 ** t, 1 - b2 ** t
        p = jax.tree.map(lambda x, a, b: (x.astype(jnp.float32) - lr * (a / bc1)
                                          / (jnp.sqrt(b / bc2) + eps)
                                          ).astype(p_dtype), p, m, v)
        cast = lambda tree: jax.tree.map(lambda a: a.astype(p_dtype), tree)
        return p, cast(m), cast(v)

    zeros = jax.jit(lambda dt: jax.tree.map(lambda x: jnp.zeros(x.shape, dt),
                                            params), static_argnums=0)

    @functools.partial(jax.jit, donate_argnums=(0,))
    def add_mean(acc, s):
        """acc + s / K: the aggregator's shift is the clients' mean."""
        return jax.tree.map(lambda a, b: a + b.astype(jnp.float32) / K, acc, s)

    client_dev = [devices[k % len(devices)] for k in range(K)]

    def initial_shift(k):
        return jax.device_put(zeros(s_dtype) if dsc else 0.0, client_dev[k])
    shifts = [initial_shift(k) for k in range(K)]
    m = v = None
    base = jax.random.fold_in(jax.random.fold_in(seed_key, 0x5EF), stream)
    losses, first_update, first_grad = [], None, {}
    for t in range(steps):
        u, client_loss = None, []
        for k in range(K):
            pk = jax.device_put(params, client_dev[k])
            acc, lk = None, []
            for r in range(use_rows):
                row = jax.device_put(batches[t, k * rows + r], client_dev[k])
                l, g = row_grad(pk, row)
                lk.append(l)
                acc = g if acc is None else accumulate(acc, g)
            del pk
            client_loss.append(sum(lk) / use_rows)
            if t == 0:
                for name, n in leaf_norms(acc).items():
                    first_grad[name] = first_grad.get(name, 0.0) + n / use_rows / K
            w, shifts[k] = client_wire(acc, shifts[k],
                                       jax.random.fold_in(base, t * K + k))
            if fault in ("frozen", "stale_shift"):
                shifts[k] = initial_shift(k)
            u = share(u, jax.device_put(w, dev0), k=k)
            del acc, w
        losses.append(sum(map(float, client_loss)) / K)
        log(f"reference round {t + 1}")
        if t == 0:
            first_update = leaf_norms(u)
            m, v = zeros(p_dtype), zeros(p_dtype)
        if fault == "frozen":
            first_update = {k: 0.0 for k in first_update}
            del u
            continue
        params, m, v = adam(params, m, v, u, jnp.float32(t + 1))
    del m, v
    shift = None
    if dsc:
        per_client = [leaf_norms(x) for x in shifts]
        clients = {n: sum(c[n] ** 2 for c in per_client) ** 0.5
                   for n in per_client[0]}
        agg = jax.device_put(zeros(jnp.float32), dev0)
        for k in range(K):
            agg = add_mean(agg, jax.device_put(shifts[k], dev0))
            shifts[k] = None
        shift = shift_readings(clients, leaf_norms(agg))
        del agg
    del shifts
    jax.block_until_ready(params)
    log("reference rounds done")
    change = change_norms(params, conf, k_w)
    return Readings(losses, first_update, change, first_grad, shift)


@functools.partial(jax.jit, static_argnames=("conf_json",))
def _change(params, k_w, conf_json):
    conf = json.loads(conf_json)
    p0 = model(conf).init(k_w, conf)
    return [jnp.linalg.norm(a.astype(jnp.float32) - b.astype(jnp.float32))
            for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(p0))]


def change_norms(params, conf: dict, k_w) -> dict:
    """Per leaf ||params - init(k_w)||, the initial weights made again on
    the device (the key is an argument, so the weights are not folded
    into the program as constants)."""
    out = _change(params, k_w, json.dumps(conf, sort_keys=True))
    return dict(zip(leaf_names(params), map(float, jax.device_get(out))))
