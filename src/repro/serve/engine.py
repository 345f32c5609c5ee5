"""Paged-KV continuous-batching serve engine (repro.serve v2).

EdgeCIM's workload is autoregressive decode — batched GEMV over a
growing KV cache — and the memory that cache wastes is the edge
bottleneck.  v2 replaces the seed's fixed-slot engine + dense
(n_slots, max_seq) cache with:

  allocator  (paged_cache.BlockAllocator) — refcounted free-list over
                                            KV pages (shared via prefix
                                            cache / fork, copy-on-write)
  prefix     (prefix.PrefixIndex)         — radix trie over committed
                                            prompt pages; admission
                                            adopts matched prefixes so
                                            prefill skips them
  scheduler  (scheduler.Scheduler)        — admission control, priority,
                                            deadlines, chunked prefill
  engine     (this file)                  — dynamic decode batch against
                                            the paged pool, streaming
                                            callbacks, preemption
  telemetry  (telemetry.Telemetry)        — TTFT/TPOT/queue percentiles,
                                            KV occupancy

The cache layer is a unified per-layer DECODE STATE: attention layers
keep paged KV pages, recurrent layers (mamba2 conv+SSM state, m/sLSTM
cells) keep fixed-size per-lane slots in a pooled StateArena
(serve/state.py).  Both are flattened into one cache dict for
`DecoderLM.serve_step`, so admission, chunked prefill, per-lane
sampling, deadlines, and preemption are IDENTICAL for every family —
hybrid zamba interleaves paged attention layers with arena layers in
one lane, and recurrent prefill is one masked-scan device call per
chunk, not one call per token.

Every step runs at most two jitted graphs with shape-stable arguments:
one chunked BATCH PREFILL call (b = max_batch, s = prefill_chunk; the
program `jit_serve_prefill`) and one decode call — `DecoderLM.serve_step`
(b = max_batch, s = 1; the program `jit_serve_decode`), or,
when the engine is built with a `repro.spec.SpecConfig`, one
`paged_verify_step` (b = max_batch, s = k + 1) that verifies a drafted
window and emits a variable number of tokens per lane (speculative
decoding; see repro/spec/).  Per-lane positions make one sequence's
prefill unable to clobber another's cache rows (the seed
`_prefill_slot` bug).

Prefix caching and speculative decoding remain attention-only
capabilities: adopting or rolling back KV pages cannot adopt or roll
back a recurrent state, so requesting either on a model with recurrent
state layers raises a ValueError naming the capability (never silent
state corruption).  `ServeEngine` + `Request` remain as the seed-API
shim; every token-input family now routes to the paged runtime.
"""
from __future__ import annotations

import functools
import time
import warnings
from dataclasses import dataclass, field, replace as dc_replace
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import DecoderLM
from repro.obs.energy import EnergyMeter
from repro.obs.recorder import FlightRecorder
from repro.obs.trace import get_tracer
from repro.quant.ptq import quantize_params
from repro.quant.qarray import QTensor, dequant_counters

from .config import ServeConfig
from .paged_cache import PagedKVCache
from .prefix import PrefixIndex
from .sampling import SamplingParams, processed_probs, sample_tokens
from .scheduler import Scheduler, ServeRequest
from .state import StateArena
from .telemetry import Telemetry


# attention-only capability guards: one message source for the engine
# and the launcher, so the policy and its wording cannot drift apart
_CAPABILITY_REASONS = {
    "speculative-decoding": "verify/rollback cannot rewind",
    "prefix-cache": "page adoption cannot reproduce",
    "parallel-sampling": "forked KV pages cannot clone",
}


def capability_error(model: DecoderLM, capability: str) -> str:
    return (f"capability {capability!r} requires a paged-attention-only "
            f"model; family {model.cfg.family!r} carries recurrent "
            f"per-lane state that {_CAPABILITY_REASONS[capability]}")


_UNSET = object()
_legacy_warned = False      # deprecation shim warns once per process

_KV_DTYPE_NAMES = {"bfloat16": "bf16", "float32": "f32", "int8": "int8"}


def _config_from_legacy(max_batch, max_seq, page_size, n_pages,
                        prefill_chunk, kv_dtype, eos_id, seed,
                        prefix_cache) -> ServeConfig:
    """Map the pre-ServeConfig kwargs onto a ServeConfig (fp precision:
    the old engine always served float weights)."""
    kv = "bf16" if kv_dtype is _UNSET else \
        _KV_DTYPE_NAMES[jnp.dtype(kv_dtype).name]
    return ServeConfig(
        precision="fp", kv_dtype=kv,
        max_batch=8 if max_batch is _UNSET else max_batch,
        max_seq=256 if max_seq is _UNSET else max_seq,
        page_size=16 if page_size is _UNSET else page_size,
        n_pages=None if n_pages is _UNSET else n_pages,
        prefill_chunk=16 if prefill_chunk is _UNSET else prefill_chunk,
        eos_id=None if eos_id is _UNSET else eos_id,
        seed=0 if seed is _UNSET else seed,
        prefix_cache=None if prefix_cache is _UNSET else prefix_cache)


def serve_prefill(model, params, state, inputs, tables, lengths, n_new):
    """`model.serve_step` for a `(b, s > 1)` chunked prefill.  Jitted
    with the model static, it is the program `jit_serve_prefill`, which
    a device trace tells apart from the decode program; being one
    module-level function, every engine on a model shares its compile."""
    return model.serve_step(params, state, inputs, tables, lengths, n_new)


def serve_decode(model, params, state, inputs, tables, lengths, n_new):
    """`model.serve_step` for a `(b, 1)` decode step: the program
    `jit_serve_decode`."""
    return model.serve_step(params, state, inputs, tables, lengths, n_new)


class PagedServeEngine:
    def __init__(self, model: DecoderLM, params: Any,
                 config: Optional[ServeConfig] = None, *,
                 max_batch=_UNSET, max_seq=_UNSET, page_size=_UNSET,
                 n_pages=_UNSET, prefill_chunk=_UNSET, kv_dtype=_UNSET,
                 eos_id=_UNSET, seed=_UNSET,
                 spec: Optional[Any] = None,
                 prefix_cache=_UNSET,
                 clock=time.monotonic,
                 devices: Optional[List[Any]] = None):
        legacy = {k: v for k, v in [
            ("max_batch", max_batch), ("max_seq", max_seq),
            ("page_size", page_size), ("n_pages", n_pages),
            ("prefill_chunk", prefill_chunk), ("kv_dtype", kv_dtype),
            ("eos_id", eos_id), ("seed", seed),
            ("prefix_cache", prefix_cache)] if v is not _UNSET}
        if config is None:
            if legacy:
                global _legacy_warned
                if not _legacy_warned:
                    _legacy_warned = True
                    warnings.warn(
                        "PagedServeEngine(max_batch=..., kv_dtype=..., ...)"
                        " kwargs are deprecated; pass a"
                        " serve.ServeConfig instead",
                        DeprecationWarning, stacklevel=2)
            config = _config_from_legacy(
                max_batch, max_seq, page_size, n_pages, prefill_chunk,
                kv_dtype, eos_id, seed, prefix_cache)
        elif legacy:
            raise ValueError(
                "pass either a ServeConfig or legacy kwargs, not both: "
                + ", ".join(sorted(legacy)))
        if (config.kv_dtype == "auto"
                and config.resolved_kv_dtype() == jnp.int8
                and model.cfg.attn_kind == "mla"):
            # auto means "best supported": MLA latent pools stay float
            # (attention.paged_cache_spec rejects int8 for them), so
            # auto degrades to bf16 instead of crashing — only an
            # EXPLICIT kv_dtype="int8" is a capability error.  Pin the
            # resolution into the config so /metrics reports what the
            # engine actually allocated.
            config = dc_replace(config, kv_dtype="bf16")
        self.config = config
        max_batch, max_seq = config.max_batch, config.max_seq
        page_size, n_pages = config.page_size, config.n_pages
        prefill_chunk, eos_id = config.prefill_chunk, config.eos_id
        seed, prefix_cache = config.seed, config.prefix_cache
        kv_dtype = config.resolved_kv_dtype()
        # tensor parallelism: a ("model",) mesh of tp devices (the
        # first tp of `devices`, else of jax.devices()).  Raises here —
        # not at first step — when tp does not divide the model's
        # head/FFN dims or the backend lacks the devices.
        self.mesh = None
        if config.tp > 1:
            from repro.dist import serve_mesh
            model.validate_tp(config.tp)
            self.mesh = serve_mesh(config.tp, devices)
        if config.quantized() and not any(
                isinstance(l, QTensor) for l in jax.tree_util.tree_leaves(
                    params, is_leaf=lambda x: isinstance(x, QTensor))):
            # launcher may hand us raw float params; the precision field
            # is authoritative, so quantize here
            params = quantize_params(params, bits=config.weight_bits(),
                                     group=config.quant_group)
        assert model.cfg.embed_inputs, "engine serves token-input models"
        assert max_seq % page_size == 0, (max_seq, page_size)
        # capability guards: prefix sharing and speculative decoding act
        # on attention KV pages alone; a model with recurrent state
        # layers cannot adopt or roll back that state, so asking is a
        # hard error — never silent state corruption
        if spec is not None and not model.supports_paged():
            raise ValueError(capability_error(model,
                                             "speculative-decoding"))
        if prefix_cache is None:        # auto: on iff fully paged
            prefix_cache = model.supports_paged()
        elif prefix_cache and not model.supports_paged():
            raise ValueError(capability_error(model, "prefix-cache"))
        self.model = model
        self.params = params
        self.max_batch = max_batch
        self.max_seq = max_seq
        self.eos_id = eos_id
        self._clock = clock
        if n_pages is None:      # dense-equivalent worst case: never OOM
            n_pages = max_batch * (max_seq // page_size)
        # unified per-layer decode state: paged KV pools for attention
        # layers (block tables, COW, ...) plus a StateArena of per-lane
        # slots for recurrent layers.  PagedKVCache doubles as the
        # token-budget ledger for families with no attention at all
        # (pools == {}): pages_needed gates admission and growth
        # uniformly, so scheduler and preemption logic are
        # family-agnostic.
        state_specs = model.decode_state_specs(max_batch, n_pages,
                                               page_size, kv_dtype)
        self.cache = PagedKVCache(model, n_pages, page_size, max_seq,
                                  kv_dtype, specs=state_specs["paged"])
        self.arena: Optional[StateArena] = (
            StateArena(model, max_batch, specs=state_specs["arena"])
            if model.has_recurrent_state() else None)
        self._paged_keys = tuple(self.cache.pools)
        self._state_shardings = None
        if self.mesh is not None:
            self._shard_runtime_state(state_specs)
        elif devices is not None:
            # one device: commit weights and state there, and every
            # jitted step follows its committed arguments
            from jax.sharding import SingleDeviceSharding
            on = SingleDeviceSharding(devices[0])
            self.params = jax.device_put(self.params, on)
            self.cache.pools = jax.device_put(self.cache.pools, on)
            if self.arena is not None:
                self.arena.state = jax.device_put(self.arena.state, on)
        # prefix sharing: committed prompt pages live in a radix trie and
        # are adopted by later requests with the same prefix (see
        # prefix.py); allocation pressure evicts trie-only pages LRU
        self.prefix: Optional[PrefixIndex] = None
        if prefix_cache:
            self.prefix = PrefixIndex(self.cache.allocator, page_size)
            self.cache.prefix_index = self.prefix
        self.scheduler = Scheduler(max_batch,
                                   prefill_chunk=min(prefill_chunk, max_seq))
        self.telemetry = Telemetry()
        # observability: process tracer (opt-in, /debug/trace), always-on
        # flight recorder (postmortem ring; replica sets the label), and
        # the CIM energy meter (simulated J / tokens-per-J in summary())
        self.tracer = get_tracer()
        self.scheduler.tracer = self.tracer
        self.recorder = FlightRecorder(label="engine", clock=clock)
        # the energy meter charges at the SERVED precision: int4 hits
        # the paper's CIM operating point (e_mac_int4, 4 bit-serial
        # passes), fp pays 16-bit storage and pass counts
        self.energy = EnergyMeter(
            model.cfg, w_bits=config.weight_bits(),
            a_bits=8 if config.quantized() else 16, tp=config.tp)
        self._cow_seen = 0          # deltas -> cow_copy / prefix_evict
        self._evict_seen = 0        # trace instants per step
        self.lanes: List[Optional[ServeRequest]] = [None] * max_batch
        # the serve step as two named programs, so a device trace tells
        # the (b, s > 1) chunked prefill from the (b, 1) decode
        self._prefill_fn = self._jit_step(serve_prefill)
        self._decode_fn = self._jit_step(serve_decode)
        self._step_fn = self._serve_step
        self._key = jax.random.PRNGKey(seed)
        self._next_eid = 0
        if spec is not None:            # SpecConfig -> speculative decode
            from repro.spec import SpecDecoder
            self.spec: Optional[SpecDecoder] = SpecDecoder(
                model, spec, max_batch=max_batch, max_seq=max_seq,
                kv_dtype=kv_dtype)
            if self.mesh is not None:
                # the verify window runs the very same sharded layout
                # as decode (the draft model stays single-device: it is
                # deliberately small enough not to need the mesh)
                self.spec.verify_fn = functools.partial(
                    self._jit_step(type(model).paged_verify_step), model)
        else:
            self.spec = None

    # -- tensor parallelism --------------------------------------------
    def _shard_runtime_state(self, state_specs) -> None:
        """Commit weights, KV pools, and arena slots to the serve mesh.

        Weights shard by their declared TP axes (QTensor leaves keep
        data and scales on one consistent pspec — see
        dist.qtree_shardings); pool leaves shard on the KV-head group
        dim (and the matching INT8 scale-pool dim), page axis
        replicated so the host-side block tables stay per-shard
        identical; arena leaves shard their cell head dims with the
        lane axis replicated.  Everything host-fed (tokens, tables,
        lengths) enters uncommitted and is replicated by GSPMD."""
        from repro.dist import (SERVE_RULES, qtree_shardings, replicated,
                                tree_shardings)
        mesh = self.mesh
        self._replicated = replicated(mesh)
        self.params = jax.device_put(
            self.params, qtree_shardings(self.model.param_specs(),
                                         self.params, mesh, SERVE_RULES))
        pool_sh = tree_shardings(state_specs["paged"], mesh, SERVE_RULES)
        self.cache.pools = jax.device_put(self.cache.pools, pool_sh)
        self._state_shardings = dict(pool_sh)
        if self.arena is not None:
            arena_sh = tree_shardings(state_specs["arena"], mesh,
                                      SERVE_RULES)
            self.arena.state = jax.device_put(self.arena.state, arena_sh)
            self._state_shardings.update(arena_sh)

    def _jit_step(self, fn):
        """Jit a (model, params, state, inputs, tables, lengths, n_new)
        step, the model static, as the program `jit_<fn name>`, the name
        a device trace shows.

        tp == 1: plain jit, byte-for-byte the pre-TP path.  tp > 1: the
        step traces inside `use_mesh_rules`, so the model's
        `constrain(..)` hints become real sharding constraints, and
        out_shardings pin logits replicated (host sampling reads one
        gathered copy) and the returned state back onto its canonical
        pool/arena shardings — donation then reuses the input buffers
        shard-for-shard and the layout can never drift step to step."""
        if self.mesh is None:
            return jax.jit(fn, static_argnums=(0,), donate_argnums=(2,))
        from repro.dist import SERVE_RULES, use_mesh_rules
        mesh = self.mesh

        def traced(model, params, state, inputs, tables, lengths, n_new):
            with use_mesh_rules(mesh, SERVE_RULES):
                return fn(model, params, state, inputs, tables, lengths,
                          n_new)

        traced.__name__ = traced.__qualname__ = fn.__name__
        return jax.jit(traced, static_argnums=(0,), donate_argnums=(2,),
                       out_shardings=(self._replicated,
                                      dict(self._state_shardings)))

    def _serve_step(self, params, state, inputs, tables, lengths, n_new):
        """`DecoderLM.serve_step` through its program for the shape:
        `jit_serve_decode` for one token per lane, else
        `jit_serve_prefill`."""
        fn = (self._decode_fn if inputs["tokens"].shape[1] == 1
              else self._prefill_fn)
        return fn(self.model, params, state, inputs, tables, lengths, n_new)

    # ------------------------------------------------------------------
    def _event(self, kind: str, **fields: Any) -> None:
        """One engine lifecycle event: always lands in the flight
        recorder (postmortem ring), mirrored to the tracer as an
        instant when tracing is on."""
        self.recorder.record(kind, **fields)
        if self.tracer.enabled:
            self.tracer.instant(kind, cat="engine", **fields)

    # ------------------------------------------------------------------
    @property
    def n_running(self) -> int:
        return sum(r is not None for r in self.lanes)

    @property
    def busy(self) -> bool:
        return self.n_running > 0 or self.scheduler.n_queued > 0

    def submit(self, req: ServeRequest) -> None:
        if req.fork_from is not None and not self.model.supports_paged():
            raise ValueError(capability_error(self.model,
                                              "parallel-sampling"))
        now = self._clock()
        req.eid = self._next_eid      # rid is the caller's label and may
        self._next_eid += 1           # collide; eid keys cache/telemetry
        self.telemetry.enqueue(req.eid, now)
        self.scheduler.submit(req, now)
        self._event("submit", eid=req.eid, rid=req.trace_id,
                    prompt_len=req.prompt_len)

    def cancel(self, eid: int) -> bool:
        """Abort a submitted request wherever it is in its lifecycle —
        queued, mid-prefill, mid-decode, or preempted-with-snapshot.
        Frees its KV pages and lane (decref: pages shared with the
        prefix trie or a fork survive), releases drafter state, closes
        the telemetry trace.  Returns False when `eid` is unknown or
        already finished.  NOT thread-safe against a concurrent
        `step()`: callers off the engine thread route through the
        gateway's EngineDriver, which runs cancels between steps."""
        now = self._clock()
        queued = self.scheduler.cancel(eid)
        if queued is not None:      # mid-queue (possibly preempted: any
            queued.done = True      # saved arena snapshot dies with it)
            queued.saved_state = None
            self.telemetry.cancel(eid, now)
            self._event("cancel", eid=eid, rid=queued.trace_id,
                        where="queued")
            return True
        for lane, req in enumerate(self.lanes):
            if req is not None and req.eid == eid:
                req.done = True
                req.cancelled = True
                self.cache.release(eid)
                self.lanes[lane] = None
                if self.spec is not None:
                    self.spec.drafter.release(lane)
                self.telemetry.cancel(eid, now)
                self._event("cancel", eid=eid, rid=req.trace_id,
                            where="lane", lane=lane)
                return True
        return False

    def run(self, requests: List[ServeRequest]) -> List[ServeRequest]:
        for r in requests:
            self.submit(r)
        while self.busy:
            self.step()
        return requests

    # ------------------------------------------------------------------
    def _dispatch(self, fn, name: str, args: Dict, tokens: np.ndarray,
                  tables: np.ndarray, lengths: np.ndarray,
                  n_new: np.ndarray):
        """Run one jitted step: flatten paged pools + arena slots into
        the unified cache dict (their key sets are disjoint by
        construction), split the returned state back.  Returns
        (logits, graph seconds) — the clock stops once the device has
        produced the logits, not when the step was enqueued.  Those
        seconds are the span `name` (with `args`), split into `enqueue`
        (host-to-device copies and the call) and `device_wait`."""
        state = dict(self.cache.pools)
        if self.arena is not None:
            state.update(self.arena.state)
        tr = self.tracer
        with tr.timed(name, **args) as call:
            with tr.span("enqueue"):
                logits, state = fn(
                    self.params, state, {"tokens": jnp.asarray(tokens)},
                    jnp.asarray(tables), jnp.asarray(lengths),
                    jnp.asarray(n_new))
            with tr.span("device_wait"):
                jax.block_until_ready(logits)
        dt = call.dur_s
        if self.mesh is not None:
            # one gathered host copy: every downstream consumer
            # (sampling, logprobs, verify walks) runs on identical
            # bytes regardless of tp — the byte-identity invariant
            # lives here
            logits = jax.device_get(logits)
        if self.arena is not None:
            self.arena.state = {k: state[k] for k in self.arena.keys}
            self.cache.pools = {k: state[k] for k in self._paged_keys}
        else:
            self.cache.pools = state
        return logits, dt

    def _lane_args(self, lanes: List[int], **more: Any) -> Dict:
        """Span args of a dispatch advancing `lanes`: the request ids
        and lane count, built only while tracing."""
        if not self.tracer.enabled:
            return {}
        return dict(rids=[self.lanes[i].trace_id for i in lanes],
                    lanes=len(lanes), **more)

    def _tables(self) -> np.ndarray:
        tab = np.zeros((self.max_batch, self.cache.max_pages), np.int32)
        for i, req in enumerate(self.lanes):
            if req is not None:
                tab[i] = self.cache.table_for(req.eid)
        return tab

    def _lengths(self) -> np.ndarray:
        ln = np.zeros(self.max_batch, np.int32)
        for i, req in enumerate(self.lanes):
            if req is not None:
                ln[i] = self.cache.seqs[req.eid].length
        return ln

    def _note_live_pages(self, rows: np.ndarray) -> None:
        """Telemetry of a decode dispatch whose attention reads `rows`
        KV rows per lane: the pages they fill, against every table
        entry."""
        if self._paged_keys:
            ps = self.cache.page_size
            self.telemetry.attn_pages(
                int(((rows + ps - 1) // ps).sum()),
                self.max_batch * self.cache.max_pages)

    def _sample_rows(self, rows: jax.Array) -> np.ndarray:
        """rows: (max_batch, vocab) -> (max_batch,) tokens, per-lane
        sampling params, PRNG key threaded through the engine."""
        temp = np.zeros(self.max_batch, np.float32)
        topk = np.zeros(self.max_batch, np.int32)
        topp = np.ones(self.max_batch, np.float32)
        for i, req in enumerate(self.lanes):
            if req is not None:
                temp[i] = req.sampling.temperature
                topk[i] = req.sampling.top_k
                topp[i] = req.sampling.top_p
        self._key, sub = jax.random.split(self._key)
        return np.asarray(sample_tokens(sub, rows, temp, topk, topp))

    def _emit(self, req: ServeRequest, token: int, now: float,
              decode: bool = True, row=None) -> None:
        req.out_tokens.append(token)
        if req.logprobs and row is not None:
            req.out_logprobs.append(
                self._logprob_entropy(row, token, req.sampling))
        self.telemetry.token(req.eid, now, decode=decode)
        if req.on_token is not None:
            req.on_token(req.rid, token)

    @staticmethod
    def _logprob_entropy(row, token: int, sampling: SamplingParams):
        """(logprob, entropy) of `token` under the PROCESSED sampling
        distribution (temperature/top-k/top-p applied) — the
        distribution the token was actually drawn from, so greedy
        decoding reports logprob 0 and entropy 0.  Host-side O(vocab),
        computed only for requests that asked for logprobs."""
        p = processed_probs(np.asarray(row, np.float32),
                            sampling.temperature, sampling.top_k,
                            sampling.top_p)
        pt = float(p[token])
        nz = p[p > 0.0]
        # + 0.0 normalizes the one-hot case's -0.0 before it hits JSON
        ent = float(-np.sum(nz * np.log(nz)) + 0.0) if nz.size else 0.0
        return (float(np.log(max(pt, 1e-12))), ent)

    def _maybe_finish(self, lane: int, now: float) -> None:
        req = self.lanes[lane]
        seq = self.cache.seqs[req.eid]
        hit_eos = (self.eos_id is not None and req.out_tokens
                   and req.out_tokens[-1] == self.eos_id)
        if (len(req.out_tokens) >= req.max_new_tokens or hit_eos
                or seq.length >= self.max_seq):
            req.done = True
            self.telemetry.done(req.eid, now)
            self._event("finish", eid=req.eid, rid=req.trace_id,
                        lane=lane, tokens=len(req.out_tokens),
                        reason="eos" if hit_eos else "budget")
            if self.prefix is not None and seq.length > req.prompt_len:
                # generated-suffix caching: the finished lane's KV holds
                # prompt + generated rows — commit the full pages past
                # the prompt too, so a follow-up turn that extends this
                # completion (chat history growing turn by turn) adopts
                # them instead of re-prefilling.  Materialized tokens
                # run to seq.length (the final emitted token was never
                # fed back), and insert() only commits full pages.  The
                # prompt of a preempted-then-resumed request already
                # contains out_tokens[:prompt_folded] — appending past
                # the fold cursor keeps trie keys equal to the actual
                # page contents.
                full = np.concatenate(
                    [np.asarray(req.prompt, np.int32),
                     np.asarray(req.out_tokens[req.prompt_folded:],
                                np.int32)])[:seq.length]
                self.prefix.insert(full, seq.pages)
            self.cache.release(req.eid)
            self.lanes[lane] = None
            if self.spec is not None:
                self.spec.drafter.release(lane)

    def _preempt(self, lane: int) -> None:
        """Pool exhausted mid-decode: evict this lane and requeue it.

        Pure-recurrent families snapshot the lane's StateArena slot to
        host — constant-size, exact — and resume from it on re-admission
        without re-prefilling a single token.  Families with attention
        layers lose their KV pages at eviction, so they requeue with
        (prompt + generated) as the new prompt and rebuild everything by
        prefill when pages free up (a hybrid's restored mamba state
        would be double-advanced by that rebuild, hence no snapshot)."""
        req = self.lanes[lane]
        self._event("preempt", eid=req.eid, rid=req.trace_id, lane=lane,
                    tokens=len(req.out_tokens))
        if self.arena is not None and self.model.n_paged_layers() == 0:
            req.saved_state = self.arena.save_lane(lane)
            req.saved_length = self.cache.seqs[req.eid].length
            req.saved_prefill_done = req.prefill_done
        else:
            # fold only the tokens generated SINCE the last fold: on a
            # second preemption out_tokens[:prompt_folded] are already
            # part of the prompt, and re-appending them would rebuild
            # (and re-serve) a history with duplicated runs
            req.prompt = np.concatenate(
                [np.asarray(req.prompt, np.int32),
                 np.asarray(req.out_tokens[req.prompt_folded:],
                            np.int32)])
            req.prompt_folded = len(req.out_tokens)
            req.prefill_done = 0
        # a preempted fork child rebuilds (prompt + generated) by
        # prefill: its new prompt has diverged from the parent's pages,
        # so re-admitting through the fork path would adopt KV rows for
        # tokens it never saw — sever the link (and its skip accounting)
        req.fork_from = None
        req.forked_tokens = 0
        self.cache.release(req.eid)
        self.lanes[lane] = None
        if self.spec is not None:
            self.spec.drafter.release(lane)
        self.scheduler.submit(req, self._clock(), resubmit=True)

    # ------------------------------------------------------------------
    def step(self) -> None:
        with self.tracer.span("engine_step"):
            self._step()

    def _step(self) -> None:
        with self.tracer.span("admit"):
            self._admit()
        prefill_s = self._prefill_phase()
        if self.spec is not None:
            decode_s, decode_lanes = self._decode_phase_spec()
        else:
            decode_s, decode_lanes = self._decode_phase()
        # page-sharing machinery reports deltas, not per-call hooks:
        # surface them as per-step instants when tracing
        if self.tracer.enabled:
            if self.cache.cow_copies > self._cow_seen:
                self.tracer.instant(
                    "cow_copy", cat="engine",
                    n=self.cache.cow_copies - self._cow_seen)
            evicted = (self.prefix.pages_evicted
                       if self.prefix is not None else 0)
            if evicted > self._evict_seen:
                self.tracer.instant("prefix_evict", cat="engine",
                                    n=evicted - self._evict_seen)
        self._cow_seen = self.cache.cow_copies
        self._evict_seen = (self.prefix.pages_evicted
                            if self.prefix is not None else 0)
        # arena slots are engine lanes 1:1, so slot fill is running
        # lanes over max_batch — sampled only when an arena exists
        state_occ = (self.n_running / self.max_batch
                     if self.arena is not None else None)
        self.telemetry.step(self.cache.occupancy(), self.n_running,
                            decode_s=decode_s, prefill_s=prefill_s,
                            decode_lanes=decode_lanes,
                            state_occupancy=state_occ,
                            family=self.model.cfg.family)

    def _admit(self) -> None:
        """Admit queued requests into free lanes and set the lanes up."""
        now = self._clock()

        def _reject(r: ServeRequest) -> None:
            self.telemetry.done(r.eid, now)
            self._event("reject", eid=r.eid, rid=r.trace_id,
                        reason=r.reject_reason, truncated=r.truncated)

        for req in self.scheduler.admit(
                now, self.n_running, self.cache, on_reject=_reject):
            lane = self.lanes.index(None)
            self.lanes[lane] = req
            self.telemetry.admit(req.eid, now)
            self._event("fork_admit" if req.fork_from is not None
                        else "admit",
                        eid=req.eid, rid=req.trace_id, lane=lane,
                        prompt_len=req.prompt_len,
                        prefix_cached=req.prefix_cached,
                        resumed=req.saved_state is not None)
            if self.arena is not None:
                if req.saved_state is not None:
                    # resumed preemption: scatter the host snapshot back
                    # and pick up exactly where the lane left off
                    self.arena.restore_lane(lane, req.saved_state)
                    self.cache.seqs[req.eid].length = req.saved_length
                    req.prefill_done = req.saved_prefill_done
                    req.saved_state = None
                else:       # fresh admission must never inherit a dead
                    self.arena.reset_lane(lane)     # lane's state
            if req.fork_from is not None:   # admitted via fork (even a
                # 1-token prompt sharing 0 pages): the trie was never
                # probed, so this is not a prefix lookup/miss
                self.telemetry.fork(req.forked_tokens)
            elif self.prefix is not None:
                self.telemetry.prefix(req.prefix_cached)

    def _prefill_phase(self) -> float:
        """One chunked BATCH prefill call for every lane with prompt
        tokens left; lanes finishing their prompt sample their first
        output token from this call's logits."""
        pre = [i for i, r in enumerate(self.lanes)
               if r is not None and r.prefill_remaining > 0]
        if not pre:
            return 0.0
        tr = self.tracer
        with tr.span("build_inputs"):
            s = self.scheduler.prefill_chunk
            tokens = np.zeros((self.max_batch, s), np.int32)
            n_new = np.zeros(self.max_batch, np.int32)
            finishing = False
            for i in list(pre):
                req = self.lanes[i]
                q = self.scheduler.prefill_quota(req)
                # prompt pages were allocated at admission, but a forked
                # / resubmitted lane may start mid-page on a shared
                # page: copy-on-write it before the chunk lands
                if not self.cache.prepare_write(req.eid, q):
                    self._preempt(i)
                    pre.remove(i)
                    continue
                tokens[i, :q] = req.prompt[req.prefill_done:
                                           req.prefill_done + q]
                n_new[i] = q
                finishing |= q == req.prefill_remaining
            if not pre:
                return 0.0
            tables, lengths = self._tables(), self._lengths()
        chunk_tokens = int(n_new.sum())
        logits, dt = self._dispatch(
            self._step_fn, "prefill_chunk",
            self._lane_args(pre, tokens=chunk_tokens),
            tokens, tables, lengths, n_new)

        if finishing:       # only sample when some lane ends its prompt
            with tr.span("sample"):
                last = jnp.take_along_axis(
                    logits, jnp.asarray(np.maximum(n_new - 1, 0)
                                        )[:, None, None], axis=1)[:, 0, :]
                nxt = self._sample_rows(last)
        with tr.span("emit"):
            now = self._clock()
            for i in pre:
                req = self.lanes[i]
                q = int(n_new[i])
                req.prefill_done += q
                self.cache.seqs[req.eid].length += q
                self.telemetry.prefill_tokens += q
                if req.prefill_remaining == 0:
                    if self.prefix is not None:
                        # prompt fully materialized: commit its full
                        # pages so later requests with the same prefix
                        # skip them
                        self.prefix.insert(
                            np.asarray(req.prompt, np.int32),
                            self.cache.seqs[req.eid].pages)
                    self._emit(req, int(nxt[i]), now, decode=False,
                               row=np.asarray(last[i])
                               if req.logprobs else None)
                    self._maybe_finish(i, now)
            self.energy.charge_prefill(chunk_tokens)
            self.recorder.record("prefill_chunk", lanes=len(pre),
                                 tokens=chunk_tokens, dur_s=dt)
        return dt

    def _decode_ready(self) -> List[int]:
        """Lanes with their prompt fully cached and at least one emitted
        token (a lane that finished prefill this same step joins
        immediately: its first token is this call's input, written at
        position seqs[eid].length)."""
        return [i for i, r in enumerate(self.lanes)
                if r is not None and r.prefill_remaining == 0
                and r.out_tokens]

    def _decode_phase(self) -> tuple:
        """One token for every decode-ready lane.  Returns (graph
        seconds, lanes advanced)."""
        dec = self._decode_ready()
        if not dec:
            return 0.0, 0
        tr = self.tracer
        with tr.span("build_inputs"):
            ready = []
            for i in dec:
                req = self.lanes[i]
                # the token we feed is the last emitted one; this decode
                # call itself writes its KV row at position
                # seqs[rid].length (prepare_write also copy-on-writes a
                # shared tail page)
                if not self.cache.prepare_write(req.eid, 1):
                    self._preempt(i)
                    continue
                ready.append(i)
            if not ready:
                return 0.0, 0
            tokens = np.zeros((self.max_batch, 1), np.int32)
            n_new = np.zeros(self.max_batch, np.int32)
            for i in ready:
                req = self.lanes[i]
                tokens[i, 0] = req.out_tokens[-1]
                n_new[i] = 1
            tables, lens = self._tables(), self._lengths()
            self._note_live_pages(lens + n_new)
        logits, dt = self._dispatch(self._step_fn, "decode_step",
                                    self._lane_args(ready), tokens, tables,
                                    lens, n_new)

        with tr.span("sample"):
            nxt = self._sample_rows(logits[:, 0, :])
        with tr.span("emit"):
            now = self._clock()
            self.energy.charge_decode(len(ready), float(lens[ready].mean()))
            self.recorder.record("decode_step", lanes=len(ready), dur_s=dt)
            for i in ready:
                req = self.lanes[i]
                self.cache.seqs[req.eid].length += 1
                self._emit(req, int(nxt[i]), now,
                           row=np.asarray(logits[i, 0, :])
                           if req.logprobs else None)
                self._maybe_finish(i, now)
        return dt, len(ready)

    def _decode_phase_spec(self) -> tuple:
        """Speculative decode: draft up to k tokens per lane, verify the
        whole window in ONE `paged_verify_step` call (always
        (max_batch, k + 1) — shape-stable under jit), emit the accepted
        prefix plus the bonus token, roll rejected KV rows back.

        Lanes with `req.spec == False`, or whose drafter found nothing,
        ride the same call with an empty window — for them this IS a
        plain decode step, so greedy output is byte-identical to the
        non-speculative engine either way.
        """
        spec = self.spec
        k = spec.cfg.k              # verify graph width: ALWAYS k_max +
        k_draft = spec.current_k()  # 1; autok only narrows how much the
        dec = self._decode_ready()  # drafter proposes (no retrace)
        if not dec:
            return 0.0, 0

        tr = self.tracer
        histories: List[Optional[np.ndarray]] = [None] * self.max_batch
        smp: List[Optional[SamplingParams]] = [None] * self.max_batch
        for i in dec:
            req = self.lanes[i]
            if req.spec:
                # out_tokens past the preemption fold cursor: a resumed
                # request's prompt already holds the earlier ones
                histories[i] = np.concatenate(
                    [np.asarray(req.prompt, np.int32),
                     np.asarray(req.out_tokens[req.prompt_folded:],
                                np.int32)])
                smp[i] = req.sampling
        # drafting is part of the decode budget speculation spends —
        # timing it keeps tokens_per_s_decode (and spec_bench's speedup
        # column) honest about what a model drafter costs
        with tr.timed("spec_draft", **self._lane_args(dec)) as draft:
            prop = spec.drafter.propose(histories, k_draft, smp)
        draft_s = draft.dur_s

        with tr.span("build_inputs"):
            tokens = np.zeros((self.max_batch, k + 1), np.int32)
            n_new = np.zeros(self.max_batch, np.int32)
            ready: List[tuple] = []                 # (lane, n_draft)
            for i in dec:
                req = self.lanes[i]
                nd = int(prop.n[i]) if histories[i] is not None else 0
                # the window writes 1 + nd KV rows and may emit 1 + nd
                # tokens; cap at the sequence budget AND the request's
                # remaining token budget (no point verifying tokens
                # emitted[:budget] would discard), then shrink until the
                # pool can hold it (a shrunk window beats a preemption)
                nd = max(0, min(nd,
                                self.max_seq
                                - self.cache.seqs[req.eid].length - 1,
                                req.max_new_tokens - len(req.out_tokens)
                                - 1))
                while nd > 0 and not self.cache.prepare_write(req.eid,
                                                              1 + nd):
                    nd -= 1
                if nd == 0 and not self.cache.prepare_write(req.eid, 1):
                    self._preempt(i)
                    continue
                tokens[i, 0] = req.out_tokens[-1]
                tokens[i, 1:1 + nd] = prop.tokens[i, :nd]
                n_new[i] = 1 + nd
                ready.append((i, nd))
            if not ready:
                return 0.0, 0
            lengths = self._lengths()
            tables = self._tables()

        # nothing drafted anywhere this step: the (b, k+1) verify graph
        # would burn (k+1)x decode compute on an effectively plain step,
        # so dispatch the ordinary (b, 1) decode graph instead
        plain = all(nd == 0 for _, nd in ready)
        step_fn = self._step_fn if plain else spec.verify_fn
        step_tokens = tokens[:, :1] if plain else tokens
        self._note_live_pages(lengths + (n_new if plain
                                         else step_tokens.shape[1]))
        lanes_idx = [i for i, _ in ready]
        logits, dt = self._dispatch(
            step_fn, "spec_verify",
            self._lane_args(lanes_idx, drafted=sum(nd for _, nd in ready)),
            step_tokens, tables, lengths, n_new)
        dt += draft_s

        with tr.span("sample"):
            logits_np = np.asarray(logits)
        with tr.span("emit"):
            now = self._clock()
            drafted = accepted = n_emitted = 0
            for i, nd in ready:
                req = self.lanes[i]
                q_rows = (prop.probs[i, :nd] if prop.probs is not None
                          else None)
                n_acc, emitted = spec.accept(
                    logits_np[i, :nd + 1], tokens[i, 1:1 + nd], q_rows,
                    req.sampling)
                drafted += nd
                accepted += n_acc
                seq = self.cache.seqs[req.eid]
                seq.length += n_acc + 1         # keep input + accepted rows
                self.cache.trim(req.eid, seq.length)  # free rejected pages
                if self.eos_id is not None and self.eos_id in emitted:
                    emitted = emitted[:emitted.index(self.eos_id) + 1]
                budget = req.max_new_tokens - len(req.out_tokens)
                # emitted[j] was accepted/sampled from verify-logits row
                # j, so that row is its (target-model) logprob source
                for j, tok in enumerate(emitted[:budget]):
                    self._emit(req, tok, now,
                               row=logits_np[i, j] if req.logprobs
                               else None)
                    n_emitted += 1
                self._maybe_finish(i, now)
            self.telemetry.spec(drafted, accepted)
            spec.observe(drafted, accepted)
            self.energy.charge_decode(
                n_emitted, float(lengths[lanes_idx].mean()))
            self.recorder.record("spec_verify", lanes=len(ready),
                                 drafted=drafted, accepted=accepted,
                                 dur_s=dt)
        return dt, len(ready)

    # ------------------------------------------------------------------
    def summary(self) -> Dict[str, float]:
        s = self.telemetry.summary()
        s.update(self.energy.summary())
        # trace-time dequant counters: full_dequant counts whole-weight
        # float materializations traced into any graph this process;
        # a quantized hot path keeps the delta at 0 (api_bench asserts)
        dq = dequant_counters()
        s["weight_full_dequants"] = float(dq["full_dequant"])
        s["weight_fused_dequants"] = float(dq["fused_dequant"])
        s["cow_copies"] = float(self.cache.cow_copies)
        s["kv_pages_shared"] = float(self.cache.pages_shared)
        if self.spec is not None:
            s["spec_k_now"] = float(self.spec.current_k())
        if self.arena is not None:
            s["state_bytes"] = float(self.arena.state_bytes())
        if self.prefix is not None:
            s["prefix_pages_resident"] = float(self.prefix.n_pages)
            s["prefix_pages_evicted"] = float(self.prefix.pages_evicted)
        return s

    def throughput(self) -> float:
        """Decode-graph token rate (matches summary's
        decode_tokens_per_s; prefill time/tokens are reported
        separately)."""
        s = self.telemetry
        return s.decode_tokens / s.decode_s if s.decode_s else 0.0


# ============================================================================
# legacy compatibility shim
# ============================================================================
@dataclass
class Request:
    """Legacy request (seed API); prefer scheduler.ServeRequest."""
    prompt: np.ndarray
    max_new_tokens: int = 32
    rid: int = 0
    out_tokens: List[int] = field(default_factory=list)
    done: bool = False


class ServeEngine:
    """Seed-API shim over the paged runtime.

    Every token-input family routes to `PagedServeEngine`
    (n_slots -> max_batch, worst-case page count so old workloads can
    never OOM): attention KV lives in paged pools, recurrent state in
    per-lane StateArena slots, so recurrent families continuous-batch
    like everyone else — the old lockstep slot loop (equal-prompt-length
    grouping, one jitted call per prompt token) is gone.
    """

    def __init__(self, model: DecoderLM, params: Any, n_slots: int = 4,
                 max_seq: int = 256, greedy: bool = True,
                 sampling: Optional[SamplingParams] = None):
        self.model = model
        self.params = params
        self.n_slots = n_slots
        self.max_seq = max_seq
        self.greedy = greedy
        self.sampling = sampling
        # largest page size dividing max_seq (any max_seq works, as
        # the seed API allowed; page_size 1 = one token per page)
        page_size = next(p for p in (16, 8, 4, 2, 1)
                         if max_seq % p == 0)
        self.engine = PagedServeEngine(
            model, params, ServeConfig(
                precision="fp", kv_dtype="bf16", max_batch=n_slots,
                max_seq=max_seq, page_size=page_size,
                prefill_chunk=min(16, max_seq)))
        self.stats: Dict[str, float] = {"tokens": 0, "steps": 0,
                                        "decode_s": 0.0}

    def run(self, requests: List[Request]) -> List[Request]:
        sampling = self.sampling if self.sampling is not None else \
            SamplingParams(temperature=0.0 if self.greedy else 1.0)
        sreqs = [ServeRequest(prompt=np.asarray(r.prompt, np.int32),
                              max_new_tokens=r.max_new_tokens,
                              rid=i, sampling=sampling)
                 for i, r in enumerate(requests)]
        self.engine.run(sreqs)
        for r, sr in zip(requests, sreqs):
            r.out_tokens = sr.out_tokens
            r.done = sr.done
        t = self.engine.telemetry
        self.stats = {"tokens": t.tokens, "steps": t.steps,
                      "decode_tokens": t.decode_tokens,
                      "decode_s": t.decode_s}
        return requests

    def throughput(self) -> float:
        n = self.stats.get("decode_tokens", self.stats["tokens"])
        return n / self.stats["decode_s"] if self.stats["decode_s"] else 0.0
