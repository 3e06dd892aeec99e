"""ServeEngine: continuous batching over the paged KV cache.

The engine owns ``max_concurrency`` decode slots.  Every ``step()``:

  1. *evict* — finished requests free their blocks and leave their slot
     (their table row resets to the scratch block so the now-inactive
     row's masked writes can't alias live blocks);
  2. *admit* — waiting requests (FIFO) take free slots while the
     allocator can cover their prompt: one bucketed-jit prefill writes
     the prompt K/V into fresh blocks and samples the first token;
  3. *grow* — active requests crossing a block boundary allocate their
     next block; when the pool is exhausted the YOUNGEST active request
     is preempted (blocks freed, prefix requeued — deterministic
     sampling keys make the replayed continuation identical);
  4. *decode* — ONE fixed-shape jitted step over all slots
     (``transformer.paged_decode_step``: per-row positions, block-table
     K/V scatter, the Pallas paged-attention kernel), then row-wise
     sampling with per-request keys.

Token streams are a function of (params, prompt, SamplingParams, seed)
only — never of slot, step, or co-resident requests — so serving 8
concurrent requests emits token-identical output to serving each alone
(the acceptance gate in tests/test_serve.py).

With a ``mesh`` the engine shards the pools' kv-heads over 'model' and
the slot dim of the per-step batch over the client axes
(``dist.sharding.paged_pool_shardings`` / ``serve_batch_shardings``).
When the slot count divides the client-axis product the decode step
runs as a fully-manual ``shard_map`` (the train step's idiom): params
enter at the TP-plan layout (``dist.sharding.tp_param_in_specs``), the
body threads a ``TPRuntime`` through ``paged_decode_step`` — local head
counts, a psum after the row-parallel ``wo``, an all_gather after the
vocab-parallel unembed — and samples its own slot shard.  Inside the
manual body a ``pallas_call`` is just per-shard code, so the paged
Pallas kernel engages under TP instead of falling back to the gather
reference (GSPMD cannot partition a ``pallas_call``, which is why the
non-manual mesh fallback keeps the naive path).
"""
from __future__ import annotations

import collections
import dataclasses
import time
from typing import Deque, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import transformer as tr
from repro.models.config import ModelConfig
from repro.serve import cache as pc
from repro.serve.sampling import SamplingParams, sample


@dataclasses.dataclass(frozen=True)
class ServeSettings:
    """Serving configuration (the ``TrainSettings`` twin for the other
    end of the checkpoint handoff)."""
    max_concurrency: int = 8       # decode slots (the continuous batch)
    block_size: int = 16           # tokens per KV block
    num_blocks: int = 128          # pool budget incl. the scratch block
    max_model_len: int = 256       # prompt + generation cap per request
    prefill_bucket: int = 32       # prompts pad up to a bucket multiple
                                   # (one prefill compile per bucket)
    max_new_tokens: int = 32       # default generation budget
    cache_dtype: str = "bfloat16"
    decode_kernel: str = "auto"    # auto | pallas | naive
    window: Optional[int] = None   # sliding window (None: cfg's own)
    eos_id: Optional[int] = None
    sampling: SamplingParams = SamplingParams()
    seed: int = 0

    def __post_init__(self):
        if self.max_concurrency < 1:
            raise ValueError(f"ServeSettings.max_concurrency must be >= 1, "
                             f"got {self.max_concurrency}")
        if self.num_blocks < 2:
            raise ValueError(f"ServeSettings.num_blocks must be >= 2, "
                             f"got {self.num_blocks}")
        if self.block_size < 1:
            raise ValueError(f"ServeSettings.block_size must be >= 1, "
                             f"got {self.block_size}")
        if self.max_model_len < 1:
            raise ValueError(f"ServeSettings.max_model_len must be >= 1, "
                             f"got {self.max_model_len}")
        if self.prefill_bucket < 1:
            raise ValueError(f"ServeSettings.prefill_bucket must be >= 1, "
                             f"got {self.prefill_bucket}")
        if self.decode_kernel not in ("auto", "pallas", "naive"):
            raise ValueError(f"ServeSettings.decode_kernel must be "
                             f"auto|pallas|naive, got {self.decode_kernel}")

    @property
    def max_pages(self) -> int:
        return pc.pages_for(self.max_model_len, self.block_size)


@dataclasses.dataclass
class Request:
    rid: int
    prompt: List[int]
    max_new_tokens: int
    sampling: SamplingParams
    seed: int
    generated: List[int] = dataclasses.field(default_factory=list)
    blocks: List[int] = dataclasses.field(default_factory=list)
    slot: int = -1
    submit_t: float = 0.0
    first_token_t: Optional[float] = None
    finish_t: Optional[float] = None
    finish_reason: str = ""
    preemptions: int = 0


@dataclasses.dataclass(frozen=True)
class RequestOutput:
    rid: int
    prompt: List[int]
    tokens: List[int]
    finish_reason: str          # stop | length
    ttft_s: float               # submit -> first token
    latency_s: float            # submit -> finish
    preemptions: int


class ServeEngine:
    """See module docstring.  ``submit`` + ``step`` for streaming use,
    ``run`` to drain a batch of prompts."""

    def __init__(self, cfg: ModelConfig, params,
                 settings: ServeSettings = ServeSettings(), mesh=None):
        if cfg.family not in tr.paged_families():
            raise ValueError(
                f"ServeEngine serves families {tr.paged_families()}; "
                f"{cfg.family!r} needs a dense per-request state "
                f"(use transformer.decode_step)")
        self.cfg = cfg
        self.settings = settings
        self.mesh = mesh
        self.window = (settings.window if settings.window is not None
                       else cfg.sliding_window)
        C, P = settings.max_concurrency, settings.max_pages
        dtype = jnp.dtype(settings.cache_dtype)
        pools = tr.init_paged_pools(cfg, settings.num_blocks,
                                    settings.block_size, dtype)
        self._manual = False
        self._pool_sh = None
        if mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec
            from repro.dist import sharding as sh
            n_dev = int(np.prod(mesh.devices.shape))
            sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
            model = int(sizes.get("model", 1))
            # decode-safe TP plan: one-token queries have no sequence to
            # shard, so the seq/ctx activation regions drop out — the
            # PARAM layout is untouched (those flags never move weights)
            self._tp_plan = dataclasses.replace(
                tr.tp_plan(cfg, model), seq=False, seq_ce=False, ctx=1)
            self._model_size = model
            # manual path: every client position must own a whole number
            # of decode slots for the slot dim to enter sharded
            self._manual = C % max(n_dev // model, 1) == 0
            self._batch_sh = sh.serve_batch_shardings(mesh)
            self._rep_sh = NamedSharding(mesh, PartitionSpec())
            if self._manual:
                pspecs = sh.tp_param_in_specs(cfg, mesh)
                params = jax.device_put(params, jax.tree.map(
                    lambda s: NamedSharding(mesh, s), pspecs,
                    is_leaf=lambda x: isinstance(x, PartitionSpec)))
                # plan.attn implies kv-head divisibility; without it the
                # pools replicate and each model shard runs full heads
                pool_spec = (PartitionSpec(None, None, "model", None, None)
                             if self._tp_plan.attn else PartitionSpec())
                self._pool_sh = {"k": NamedSharding(mesh, pool_spec),
                                 "v": NamedSharding(mesh, pool_spec)}
                pools = jax.device_put(pools, self._pool_sh)
                self._midx = jax.device_put(
                    jnp.arange(model, dtype=jnp.int32),
                    NamedSharding(mesh, PartitionSpec("model")))
                bspec = self._batch_sh.spec
                self._decode_body = sh.shard_map(
                    self._manual_decode_fn, mesh,
                    in_specs=(PartitionSpec("model"), pspecs,
                              {"k": pool_spec, "v": pool_spec},
                              bspec, bspec, bspec, bspec, bspec, bspec,
                              bspec),
                    out_specs=(bspec, {"k": pool_spec, "v": pool_spec}))
            else:
                params = jax.device_put(
                    params, sh.param_shardings(cfg, mesh, "use"))
                self._pool_sh = sh.paged_pool_shardings(cfg, mesh)
                pools = jax.device_put(pools, self._pool_sh)
        if settings.decode_kernel == "auto":
            # the kernel is fine meshless and inside the manual body; it
            # is only the GSPMD fallback that cannot partition it
            self._use_kernel = mesh is None or self._manual
            if not self._use_kernel:
                from repro.kernels.common import log_fallback
                log_fallback("paged_attention", f"{C} decode slots do not "
                             f"divide over the mesh's client positions")
        else:
            self._use_kernel = settings.decode_kernel == "pallas"
        self.params = params
        self.pools = pools
        self.allocator = pc.BlockAllocator(settings.num_blocks,
                                           settings.block_size)
        self.tables = np.zeros((C, P), np.int32)       # scratch block 0
        self.slots: List[Optional[Request]] = [None] * C
        self.waiting: Deque[Request] = collections.deque()
        self._next_rid = 0
        self._steps = 0
        self._tokens_out = 0
        self._t0: Optional[float] = None
        if self._manual:
            midx = self._midx
            body = self._decode_body
            self._decode = jax.jit(
                lambda params, pools, *rest: body(midx, params, pools,
                                                  *rest),
                donate_argnums=(1,))
        else:
            self._decode = jax.jit(self._decode_fn, donate_argnums=(1,))
        self._prefills: dict = {}

    # ------------------------------------------------------ device closures
    def _decode_fn(self, params, pools, tables, ctxs, toks, keys,
                   temps, tks, tps):
        logits, pools = tr.paged_decode_step(
            params, self.cfg, pools, tables, ctxs, toks,
            window=self.window, use_kernel=self._use_kernel)
        nxt = sample(keys, logits[:, 0], temps, tks, tps)
        return nxt, pools

    def _manual_decode_fn(self, midx, params, pools, tables, ctxs, toks,
                          keys, temps, tks, tps):
        """shard_map body: every array is this position's shard — params
        at their TP dims, pools at the local kv-heads, the slot batch at
        this client coordinate's rows.  ``midx`` is the model-axis
        coordinate fed in as a sharded arange (``axis_index`` is
        unsupported under fully-manual SPMD)."""
        tp_rt = (tr.TPRuntime("model", self._model_size, midx[0],
                              self._tp_plan)
                 if self._tp_plan.active else None)
        logits, pools = tr.paged_decode_step(
            params, self.cfg, pools, tables, ctxs, toks,
            window=self.window, use_kernel=self._use_kernel, tp=tp_rt)
        nxt = sample(keys, logits[:, 0], temps, tks, tps)
        return nxt, pools

    def _prefill_fn(self, params, pools, tokens, pages, last, key,
                    temp, tk, tp_):
        logits, caches, _ = tr.forward(params, self.cfg, tokens,
                                       mode="prefill", window=self.window)
        pools = pc.write_prefill(pools, caches["kv"]["k"][:, 0],
                                 caches["kv"]["v"][:, 0], pages,
                                 self.settings.block_size)
        first = sample(key[None], logits[0, last][None], temp[None],
                       tk[None], tp_[None])[0]
        return first, pools

    def _prefill(self, bucket: int):
        fn = self._prefills.get(bucket)
        if fn is None:
            if self.mesh is not None:
                # pin the pool output to the committed layout so the
                # decode step (whose specs assume it) never re-lowers
                fn = jax.jit(self._prefill_fn, donate_argnums=(1,),
                             out_shardings=(self._rep_sh, self._pool_sh))
            else:
                fn = jax.jit(self._prefill_fn, donate_argnums=(1,))
            self._prefills[bucket] = fn
        return fn

    # -------------------------------------------------------------- intake
    def submit(self, prompt: Sequence[int], *,
               max_new_tokens: Optional[int] = None,
               sampling: Optional[SamplingParams] = None,
               seed: Optional[int] = None) -> int:
        """Queue a request; returns its id.  ``seed`` defaults to the
        request id (folded with ``settings.seed``) — pass one explicitly
        to make a prompt's stream reproducible across engines."""
        prompt = list(map(int, prompt))
        if not prompt:
            raise ValueError("empty prompt")
        new = (max_new_tokens if max_new_tokens is not None
               else self.settings.max_new_tokens)
        if len(prompt) + new > self.settings.max_model_len:
            raise ValueError(
                f"prompt ({len(prompt)}) + max_new_tokens ({new}) exceeds "
                f"max_model_len ({self.settings.max_model_len})")
        if pc.pages_for(len(prompt) + new, self.settings.block_size) > \
                self.allocator.capacity:
            raise ValueError(
                f"request needs more blocks than the pool holds "
                f"(num_blocks={self.settings.num_blocks})")
        rid = self._next_rid
        self._next_rid += 1
        r = Request(rid=rid, prompt=prompt, max_new_tokens=new,
                    sampling=sampling or self.settings.sampling,
                    seed=self.settings.seed * 1_000_003 + (
                        seed if seed is not None else rid),
                    submit_t=time.monotonic())
        self.waiting.append(r)
        return rid

    # ------------------------------------------------------------ plumbing
    def _token_key(self, r: Request, i: int):
        return jax.random.fold_in(jax.random.PRNGKey(r.seed), i)

    def _active(self) -> List[Request]:
        return [r for r in self.slots if r is not None]

    def _ctx_len(self, r: Request) -> int:
        # tokens whose K/V is in cache: prompt + all generated but the
        # newest (the pending decode step writes that one)
        return len(r.prompt) + len(r.generated) - 1

    def _put_batch(self, x):
        if self.mesh is not None:
            return jax.device_put(x, self._batch_sh)
        return x

    def _evict(self, r: Request, reason: str) -> RequestOutput:
        self.allocator.free(r.blocks)
        r.blocks = []
        self.tables[r.slot, :] = pc.SCRATCH_BLOCK
        self.slots[r.slot] = None
        r.slot = -1
        r.finish_t = time.monotonic()
        r.finish_reason = reason
        return RequestOutput(
            rid=r.rid, prompt=r.prompt, tokens=list(r.generated),
            finish_reason=reason,
            ttft_s=(r.first_token_t or r.finish_t) - r.submit_t,
            latency_s=r.finish_t - r.submit_t, preemptions=r.preemptions)

    def _preempt_youngest(self) -> bool:
        """Free the most recently admitted active request and requeue its
        full prefix at the head of the line.  Its sampling keys are
        indexed by token position, so the replay continues the exact
        same stream."""
        victims = [r for r in self.slots if r is not None]
        if len(victims) <= 1:
            return False
        v = max(victims, key=lambda r: r.rid)
        self.allocator.free(v.blocks)
        v.blocks = []
        self.tables[v.slot, :] = pc.SCRATCH_BLOCK
        self.slots[v.slot] = None
        v.slot = -1
        v.preemptions += 1
        self.waiting.appendleft(v)
        return True

    def _admit(self, r: Request, slot: int) -> bool:
        """Prefill ``r``'s prefix (prompt + any pre-preemption tokens)
        into fresh blocks; samples token index len(generated)."""
        s = self.settings
        prefix = r.prompt + r.generated
        n_pages = pc.pages_for(len(prefix) + 1, s.block_size)
        blocks = self.allocator.alloc(n_pages)
        if blocks is None:
            return False
        bucket = -(-len(prefix) // s.prefill_bucket) * s.prefill_bucket
        toks = np.zeros((1, bucket), np.int32)
        toks[0, :len(prefix)] = prefix
        # fixed-length page vector (stable jit shapes); pad entries point
        # at the scratch block, so the bucket's padded tail lands there
        pages = np.full((max(s.max_pages, pc.pages_for(bucket, s.block_size),
                             n_pages),), pc.SCRATCH_BLOCK, np.int32)
        pages[:n_pages] = blocks
        samp = r.sampling
        first, self.pools = self._prefill(bucket)(
            self.params, self.pools, jnp.asarray(toks), jnp.asarray(pages),
            len(prefix) - 1, self._token_key(r, len(r.generated)),
            jnp.float32(samp.temperature), jnp.int32(samp.top_k),
            jnp.float32(samp.top_p))
        r.generated.append(int(first))
        if r.first_token_t is None:
            r.first_token_t = time.monotonic()
        self._tokens_out += 1
        r.slot = slot
        r.blocks = blocks
        self.slots[slot] = r
        self.tables[slot, :] = pc.SCRATCH_BLOCK
        self.tables[slot, :n_pages] = blocks
        return True

    # ---------------------------------------------------------------- step
    def step(self) -> List[RequestOutput]:
        """One engine iteration: evict / admit / grow / batched decode.
        Returns the requests that finished during this step."""
        s = self.settings
        if self._t0 is None:
            self._t0 = time.monotonic()
        self._steps += 1
        finished: List[RequestOutput] = []

        # evict finished (incl. first-token-only completions from admit)
        for r in list(self._active()):
            if self._done(r):
                finished.append(self._evict(r, self._done(r)))

        # admit waiting into free slots
        for slot in range(s.max_concurrency):
            if not self.waiting or self.slots[slot] is not None:
                continue
            if not self._admit(self.waiting[0], slot):
                break
            r = self.waiting.popleft()
            if self._done(r):
                finished.append(self._evict(r, self._done(r)))

        # grow: the pending decode writes at position ctx — make sure its
        # page exists; preempt the youngest request when the pool is dry.
        # A preempted r (slot -1 — evicted by an earlier iteration's
        # preempt, possibly its own) drops out of the loop: it re-enters
        # through admission, not growth.
        for r in list(self._active()):
            while r.slot >= 0 and \
                    pc.pages_for(self._ctx_len(r) + 1, s.block_size) > \
                    len(r.blocks):
                nb = self.allocator.alloc(1)
                if nb is None:
                    if self._preempt_youngest():
                        continue
                    raise pc.BlockBudgetExceeded(
                        "pool exhausted with a single active request — "
                        "num_blocks cannot cover max_model_len")
                if r.slot < 0:
                    self.allocator.free(nb)     # r itself was preempted
                    break
                self.tables[r.slot, len(r.blocks)] = nb[0]
                r.blocks.extend(nb)

        active = self._active()
        if not active:
            return finished

        nxt, self.pools = self._decode(self.params, self.pools,
                                       *self._decode_inputs(active))
        nxt = np.asarray(nxt)
        now = time.monotonic()
        for r in active:
            r.generated.append(int(nxt[r.slot]))
            self._tokens_out += 1
            if r.first_token_t is None:
                r.first_token_t = now
            if self._done(r):
                finished.append(self._evict(r, self._done(r)))
        return finished

    def _decode_inputs(self, active: List[Request]) -> tuple:
        """The per-slot batch of one decode step for the ``active``
        requests (inactive slots hold the scratch table row)."""
        C = self.settings.max_concurrency
        toks = np.zeros((C, 1), np.int32)
        ctxs = np.zeros((C,), np.int32)
        keys = np.zeros((C, 2), np.uint32)
        temps = np.zeros((C,), np.float32)
        tks = np.zeros((C,), np.int32)
        tps = np.ones((C,), np.float32)
        for r in active:
            toks[r.slot, 0] = r.generated[-1]
            ctxs[r.slot] = self._ctx_len(r)
            keys[r.slot] = np.asarray(self._token_key(r, len(r.generated)))
            temps[r.slot] = r.sampling.temperature
            tks[r.slot] = r.sampling.top_k
            tps[r.slot] = r.sampling.top_p
        return tuple(self._put_batch(jnp.asarray(x)) for x in
                     (self.tables, ctxs, toks, keys, temps, tks, tps))

    def lower_decode(self):
        """The engine's own decode step, lowered for its params and pools
        (``jax.stages.Lowered``): its text shows whether the Pallas
        paged-attention kernel (``tpu_custom_call``) is in the program."""
        return self._decode.lower(self.params, self.pools,
                                  *self._decode_inputs([]))

    def _done(self, r: Request) -> str:
        if self.settings.eos_id is not None and r.generated and \
                r.generated[-1] == self.settings.eos_id:
            return "stop"
        if len(r.generated) >= r.max_new_tokens:
            return "length"
        return ""

    def run(self, prompts: Optional[Sequence[Sequence[int]]] = None,
            **submit_kw) -> List[RequestOutput]:
        """Submit ``prompts`` (optional) and drain the engine.  Outputs
        are returned sorted by request id."""
        for p in prompts or ():
            self.submit(p, **submit_kw)
        outs: List[RequestOutput] = []
        while self.waiting or self._active():
            outs.extend(self.step())
        return sorted(outs, key=lambda o: o.rid)

    # ---------------------------------------------------------------- misc
    def stats(self) -> dict:
        elapsed = (time.monotonic() - self._t0) if self._t0 else 0.0
        return {
            "steps": self._steps,
            "tokens_out": self._tokens_out,
            "tokens_per_s": self._tokens_out / elapsed if elapsed else 0.0,
            "peak_blocks": self.allocator.peak_used,
            "block_capacity": self.allocator.capacity,
        }

    @classmethod
    def from_checkpoint(cls, path, cfg: ModelConfig,
                        settings: ServeSettings = ServeSettings(),
                        mesh=None) -> "ServeEngine":
        """Load a ``launch/train.py`` artifact (sharded msgpack dir or
        legacy single file) and serve it — the store->use handoff: the
        checkpoint holds the FSA store layout, ``device_put`` under the
        serve mesh's ``use`` shardings does the reshard."""
        import functools
        from repro.checkpoint import msgpack_ckpt as ck
        target = jax.eval_shape(
            functools.partial(tr.init_params, cfg=cfg),
            jax.random.PRNGKey(0))
        params = ck.restore_any(path, target)
        # __init__ device_puts under the serve mesh's "use" shardings —
        # that device_put IS the store->use reshard
        return cls(cfg, params, settings, mesh=mesh)
