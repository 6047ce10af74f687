"""Slot-based continuous-batching decode scheduler (the serving subsystem).

Design (ROADMAP "real-traffic serving path"):

  * A fixed pool of ``slots`` cache rows backs one fixed-shape jitted decode
    step: the per-slot position vector lets every request advance
    independently, so new requests join and finished ones leave mid-flight
    without retracing.
  * Eviction: after ``gen`` tokens the slot returns to the free list; a
    parked slot keeps riding the batched step (fixed shapes) but its writes
    stay causally invisible to the next occupant (end-aligned: hidden
    behind the causal mask; paged: dropped through its freed block table).
  * Arrivals are measured in engine ticks (decode steps), giving a
    deterministic, machine-independent arrival process; wall-clock is used
    only for the reported latency/throughput metrics.

Cache layout / admission scenarios (``paged=`` selects the engine; one
fixed-shape jitted decode step serves both):

  | scenario           | cache layout            | admission (prefill)      | request length limit        |
  |--------------------|-------------------------|--------------------------|-----------------------------|
  | end-aligned (dflt) | per-slot (max_len) row  | ONE fused cache-writing  | prompt+gen <= max_len per   |
  |                    |                         | forward, bucketed padded | slot (<= window for SWA)    |
  | paged              | shared page arena +     | CHUNKED: fixed (1,chunk) | prompt+gen <= pool capacity |
  |                    | per-request block table | slices interleaved with  | (and the block-table width  |
  |                    | (serving/kvcache.py)    | decode ticks             | cap max_len)                |
  | recurrent fallback | state leaves (no        | per-token B=1 loop (pad  | prompt+gen <= max_len       |
  | (mamba2/m/sLSTM)   | position indexing)      | would corrupt the state) |                             |

End-aligned admission stalls every in-flight decode for a whole prompt
forward; chunked prefill bounds that stall to one ``chunk``-token slice per
tick (``costmodel.chunked_prefill_cost`` models the tradeoff) and makes
prompts of any length schedulable.  The paged engine addresses K/V through
per-request page chains (``serving.BlockPool``), so ``prompt + gen`` is
bounded by *pool capacity* rather than any per-slot rectangle — requests an
end-aligned slot must reject outright are servable
(``benchmarks/_serve_throughput.py`` measures the A/B).

The naive one-request-at-a-time server is this same engine with ``slots=1``.
Cost-model predictions come from ``costmodel.decode_step_cost`` /
``paged_decode_step_cost`` / ``prefill_cost`` / ``chunked_prefill_cost``
(``roofline --serve``).
"""
from __future__ import annotations

import time
from collections import deque
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.profiler import TraceAnnotation

from repro.config import ModelConfig, ParallelConfig
from repro.models import transformer as T
from repro.parallel import steps as S
from repro.serving import BlockPool, ring_pages, telemetry


def sample_tokens(logits: jax.Array, key: jax.Array, temperature: float,
                  top_p: float = 1.0) -> jax.Array:
    """Temperature / top-p (nucleus) sampling over ``(B, V)`` logits;
    ``temperature == 0`` is greedy argmax (the scheduler's default and the
    test oracle).  Top-p keeps the smallest prefix of the sorted
    distribution whose mass exceeds ``top_p`` (the top token always
    survives), masks the rest to -inf, and samples the renormalized tail."""
    if temperature <= 0.0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    # temperature first, nucleus second (the conventional order): the top-p
    # mass is measured on the tempered distribution, so raising T widens
    # the kept set
    logits = logits.astype(jnp.float32) / temperature
    if top_p < 1.0:
        sorted_l = jnp.sort(logits, axis=-1)[..., ::-1]
        probs = jax.nn.softmax(sorted_l, axis=-1)
        cum = jnp.cumsum(probs, axis=-1)
        keep = cum - probs < top_p            # mass before this token < p
        last = jnp.maximum(jnp.sum(keep, axis=-1) - 1, 0)
        thresh = jnp.take_along_axis(sorted_l, last[..., None], axis=-1)
        logits = jnp.where(logits >= thresh, logits, -jnp.inf)
    return jax.random.categorical(key, logits, axis=-1).astype(jnp.int32)


@dataclass(frozen=True)
class Request:
    rid: int
    prompt: Sequence[int]          # token ids; may be empty (generate from BOS)
    gen: int                       # tokens to generate, >= 1
    arrival: int = 0               # engine tick at which the request appears


@dataclass
class Completion:
    rid: int
    tokens: List[int]
    arrival: int
    admitted_tick: int
    done_tick: int
    due_s: float                   # the engine reached the arrival tick
    admitted_s: float              # (all three: wall seconds from run start)
    first_token_s: float

    @property
    def ttft_s(self) -> float:
        """Due → first token: the wait for a slot or pages, then prefill."""
        return self.first_token_s - self.due_s


@dataclass
class _Slot:
    req: Request
    tokens: List[int] = field(default_factory=list)
    admitted_tick: int = 0
    due_s: float = 0.0
    admitted_s: float = 0.0
    first_token_s: float = 0.0
    state: str = "decode"          # "prefill" while chunked prefill runs
    cursor: int = 0                # prompt tokens consumed (paged prefill)


class Scheduler:
    """Continuous-batching decode engine over a fixed slot pool (end-aligned
    cache rows, or the paged block-pool arena with ``paged=True``)."""

    def __init__(self, cfg: ModelConfig, pcfg: ParallelConfig, params, *,
                 slots: int = 4, max_len: int = 256, bucket: int = 16,
                 bos: int = 0, ctx=None, temperature: float = 0.0,
                 top_p: float = 1.0, seed: int = 0, paged: bool = False,
                 block: int = 16, pool_blocks: Optional[int] = None,
                 chunk: int = 32):
        if cfg.enc_dec:
            raise NotImplementedError("enc-dec serving is not scheduled yet")
        if slots < 1 or max_len < 2:
            raise ValueError(f"need slots >= 1 and max_len >= 2, got "
                             f"{slots}/{max_len}")
        if temperature < 0.0 or not 0.0 < top_p <= 1.0:
            raise ValueError(f"need temperature >= 0 and 0 < top_p <= 1, "
                             f"got {temperature}/{top_p}")
        if not paged and cfg.window is not None and max_len > cfg.window:
            raise NotImplementedError(
                f"slots are end-aligned: max_len {max_len} must fit the "
                f"attention window {cfg.window}")
        if hasattr(pcfg, "to_pcfg"):          # a first-class ParallelPlan
            pcfg = pcfg.to_pcfg()
        self.cfg, self.pcfg, self.params, self.ctx = cfg, pcfg, params, ctx
        self.slots, self.max_len = slots, max_len
        self.bucket, self.bos = max(1, bucket), bos
        self.temperature, self.top_p, self.seed = temperature, top_p, seed
        self.sampling = temperature > 0.0
        self.paged = paged
        self.fused = T.supports_fused_prefill(cfg)
        if paged:
            if not T.supports_paged(cfg):
                raise NotImplementedError(
                    f"paged serving needs a pure-attention pattern; "
                    f"got {cfg.period}")
            if block < 1 or chunk < 1:
                raise ValueError(f"need block >= 1 and chunk >= 1, got "
                                 f"{block}/{chunk}")
            self.block, self.chunk = block, chunk
            self.n_pages = -(-max_len // block)      # block-table width
            # windowed layers: a ring per slot, never wider than the table
            windows = [c.window for c in cfg.period_cfgs if c.window]
            self.ring_pages = min(ring_pages(max(windows), chunk, block),
                                  self.n_pages) if windows else 0
            self.pool = BlockPool(
                pool_blocks if pool_blocks is not None
                else slots * self.n_pages, block)
        if self.sampling:
            # logits-returning decode + per-tick sampling, one fused jit:
            # every slot samples from its own row (parked rows ride along)
            base = S.make_decode_step(cfg, pcfg, ctx, return_logits=True,
                                      paged=paged)
            if paged:
                def _sampled(p, tok, cache, pos, tables, key):
                    logits, new_cache = base(p, tok, cache, pos, tables)
                    return (sample_tokens(logits, key, temperature, top_p),
                            new_cache)
            else:
                def _sampled(p, tok, cache, pos, key):
                    logits, new_cache = base(p, tok, cache, pos)
                    return (sample_tokens(logits, key, temperature, top_p),
                            new_cache)

            self._decode = jax.jit(_sampled, donate_argnums=(2,))
        else:
            self._decode = jax.jit(S.make_decode_step(cfg, pcfg, ctx,
                                                      paged=paged),
                                   donate_argnums=(2,))
        if paged:
            self._chunk_prefill = jax.jit(
                S.make_chunk_prefill_step(cfg, pcfg, ctx), donate_argnums=(2,))
            self._prefill = self._prefill_logits = self._decode_greedy = None
        else:
            # unpadded per-token prefill fallback is always greedy-shaped
            # (its intermediate outputs are ignored; the last token is
            # re-sampled)
            self._decode_greedy = self._decode if not self.sampling else \
                jax.jit(S.make_decode_step(cfg, pcfg, ctx), donate_argnums=(2,))
            self._prefill = jax.jit(S.make_prefill_step(cfg, pcfg, ctx),
                                    donate_argnums=(2,)) if self.fused else None
            self._prefill_logits = jax.jit(
                S.make_decode_step(cfg, pcfg, ctx, return_logits=True),
                donate_argnums=(2,)) if self.sampling and not self.fused else None
        self._insert = jax.jit(self._insert_impl, donate_argnums=(0,))
        self.reset()

    def reset(self) -> None:
        """Fresh cache/pool + slot state and an empty submission queue (jit
        caches survive — use for warmup); the sampling stream restarts from
        the seed for reproducible runs."""
        if self.paged:
            self.cache = T.init_paged_cache(self.cfg, self.pool.n_blocks,
                                            self.block, slots=self.slots,
                                            ring_pages=self.ring_pages)
            self.pool.reset()
            self._tables = np.full((self.slots, self.n_pages), -1, np.int32)
        else:
            self.cache = T.init_cache(self.cfg, self.slots, self.max_len)
        self._tok = np.zeros((self.slots,), np.int32)
        self._pos = np.zeros((self.slots,), np.int32)
        self._key = jax.random.PRNGKey(self.seed)
        self._queue: List[Request] = []

    def _next_key(self):
        self._key, sub = jax.random.split(self._key)
        return sub

    @staticmethod
    def _insert_impl(big, small, slot):
        return jax.tree.map(
            lambda bg, sm: lax.dynamic_update_slice(
                bg, sm.astype(bg.dtype), (0, slot) + (0,) * (bg.ndim - 2)),
            big, small)

    # ------------------------------------------------------------------
    def submit(self, req: Request) -> None:
        """Validate and enqueue one request (``run`` drains the queue).
        Length limits are enforced HERE, with the limit named, instead of
        failing deep inside admission: end-aligned mode is bounded by the
        per-slot row, paged mode by pool capacity and the block-table
        width."""
        lp = len(req.prompt)
        total = lp + req.gen
        if req.gen < 1 or req.arrival < 0:
            raise ValueError(f"request {req.rid}: need gen >= 1 and "
                             f"arrival >= 0, got {req.gen}/{req.arrival}")
        if self.paged:
            if total > self.max_len:
                raise ValueError(
                    f"request {req.rid}: prompt {lp} + gen {req.gen} = "
                    f"{total} tokens exceeds the block-table width cap "
                    f"max_len={self.max_len} ({self.n_pages} pages x block "
                    f"{self.block})")
            need = self.pool.blocks_needed(total)
            if need > self.pool.n_blocks:
                raise ValueError(
                    f"request {req.rid}: prompt {lp} + gen {req.gen} = "
                    f"{total} tokens needs {need} pages, pool capacity is "
                    f"{self.pool.n_blocks} blocks x {self.block} tokens")
        elif total > self.max_len:
            raise ValueError(
                f"request {req.rid}: prompt {lp} + gen {req.gen} = {total} "
                f"tokens exceeds the end-aligned slot capacity "
                f"max_len={self.max_len}")
        self._queue.append(req)

    # ------------------------------------------------------------------
    def _bucketed(self, n: int) -> int:
        return min(self.max_len, -(-n // self.bucket) * self.bucket)

    def _admit(self, req: Request, slot: int) -> Optional[int]:
        """End-aligned admission: prefill ``req``'s prompt into ``slot``;
        returns its first token (None for an empty prompt — the first token
        then comes from the next decode step, fed from BOS).  Leaves
        ``_tok``/``_pos`` pointing at the next decode input."""
        prompt = np.asarray(req.prompt, np.int32)
        lp = int(prompt.shape[0])
        assert lp + req.gen <= self.max_len  # submit() validated
        if lp == 0:
            # no prompt: greedy generation starts from BOS at position 0 on a
            # fresh cache row — recurrent state leaves have no position
            # indexing, so the previous occupant's state must be zeroed (the
            # lp > 0 paths overwrite it via their prefill insert)
            self.cache = self._insert(self.cache,
                                      T.init_cache(self.cfg, 1, self._bucketed(1)),
                                      jnp.int32(slot))
            self._tok[slot], self._pos[slot] = self.bos, 0
            return None
        if self.fused:
            lb = self._bucketed(lp)
            toks = np.zeros((1, lb), np.int32)
            toks[0, :lp] = prompt
            batch = {"tokens": jnp.asarray(toks),
                     "length": jnp.asarray([lp], jnp.int32)}
            logits, row = self._prefill(self.params, batch,
                                        T.init_cache(self.cfg, 1, lb))
            if self.sampling:
                first = int(sample_tokens(logits, self._next_key(),
                                          self.temperature, self.top_p)[0])
            else:
                first = int(jnp.argmax(logits, axis=-1)[0])
        else:
            # recurrent state absorbs padding: unpadded per-token loop (B=1;
            # jit retraces per shape, so this reuses the decode step fn);
            # only the last prompt token's output matters — it is re-sampled
            # from its logits when sampling is on
            row = T.init_cache(self.cfg, 1, self._bucketed(lp))
            nxt = None
            for i in range(lp):
                if self.sampling and i == lp - 1:
                    lg, row = self._prefill_logits(
                        self.params, jnp.asarray(prompt[i:i + 1]), row,
                        jnp.int32(i))
                    nxt = sample_tokens(lg, self._next_key(),
                                        self.temperature, self.top_p)
                else:
                    nxt, row = self._decode_greedy(
                        self.params, jnp.asarray(prompt[i:i + 1]), row,
                        jnp.int32(i))
            first = int(nxt[0])
        self.cache = self._insert(self.cache, row, jnp.int32(slot))
        self._tok[slot], self._pos[slot] = first, lp
        return first

    def _admit_paged(self, req: Request, slot: int, st: _Slot) -> None:
        """Paged admission: reserve worst-case pages (so alloc-on-write can
        never fail mid-flight) and start the chunked prefill — no cache work
        happens here; pages are written chunk by chunk in the tick loop."""
        self.pool.admit(req.rid, len(req.prompt) + req.gen)
        self._tables[slot] = -1
        if len(req.prompt) == 0:
            # no prompt: decode from BOS at position 0; the fresh page is
            # allocated by the pre-decode ensure() and stale arena contents
            # beyond position 0 stay behind the kpos <= pos mask
            st.state = "decode"
            self._tok[slot], self._pos[slot] = self.bos, 0
            return
        st.state, st.cursor = "prefill", 0

    def _prefill_chunk_tick(self, slot: int, st: _Slot) -> Optional[jax.Array]:
        """Consume ONE ``chunk``-token slice of ``slot``'s prompt (the
        admission-stall bound: in-flight decodes wait for at most this one
        fixed-shape call per prefilling slot per tick).  Returns the first
        generated token, still on the device, when the prompt completes,
        else None; ``run`` picks it up after the tick's last chunk."""
        prompt = np.asarray(st.req.prompt, np.int32)
        lp = int(prompt.shape[0])
        lo = st.cursor
        ln = min(self.chunk, lp - lo)
        self.pool.ensure(st.req.rid, lo + ln)
        toks = np.zeros((1, self.chunk), np.int32)
        toks[0, :ln] = prompt[lo:lo + ln]
        table = self.pool.table(st.req.rid, self.n_pages)[None]
        logits, self.cache = self._chunk_prefill(
            self.params, jnp.asarray(toks), self.cache, jnp.int32(lo),
            jnp.asarray(table), jnp.int32(ln), jnp.int32(slot))
        st.cursor = lo + ln
        if st.cursor < lp:
            return None
        st.state = "decode"
        self._pos[slot] = lp
        if self.sampling:
            return sample_tokens(logits, self._next_key(), self.temperature,
                                 self.top_p)[0]
        return jnp.argmax(logits, axis=-1)[0]

    # ------------------------------------------------------------------
    def _pages(self) -> tuple:
        if not self.paged:
            return 0, 0
        return self.pool.reserved_blocks, self.pool.live_blocks

    def _expert_rows(self) -> list:
        """The expert layers' per-slot counts of the last decode step, on
        the device (``transformer.init_paged_cache``)."""
        if not self.paged:
            return []
        return [c["expert_rows"] for c in self.cache if "expert_rows" in c]

    @telemetry.gc_spans()
    def run(self, requests: Sequence[Request] = (), *,
            on_token: Optional[Callable[[int, int], None]] = None) -> dict:
        """Serve ``requests`` (plus anything already ``submit``ted) to
        completion.  Tokens stream per request through ``on_token(rid,
        token)`` (one host sync per engine tick).  Returns completions plus
        aggregate wall-time / throughput metrics (and the block pool's
        occupancy/fragmentation report in paged mode).  Each tick is tiled
        by ``serve.*`` profiler spans and logged in ``serving.telemetry``."""
        for req in requests:
            self.submit(req)
        pending = deque(sorted(self._queue, key=lambda r: (r.arrival, r.rid)))
        self._queue = []
        arrivals = list(pending)
        reached = 0                  # arrivals whose tick the engine reached
        due: Dict[int, float] = {}
        active: Dict[int, _Slot] = {}
        free = list(range(self.slots - 1, -1, -1))
        done: Dict[int, Completion] = {}
        generated = 0
        tick = 0
        t0 = time.perf_counter()

        def finish(slot: int) -> None:
            st = active.pop(slot)
            free.append(slot)
            if self.paged:
                # eviction: pages return to the pool; the dead table row
                # makes any parked-slot writes drop on the device
                self.pool.free(st.req.rid)
                self._tables[slot] = -1
            done[st.req.rid] = Completion(
                rid=st.req.rid, tokens=st.tokens, arrival=st.req.arrival,
                admitted_tick=st.admitted_tick, done_tick=tick,
                due_s=st.due_s, admitted_s=st.admitted_s,
                first_token_s=st.first_token_s)

        def emit(slot: int, tok: int) -> None:
            nonlocal generated
            st = active[slot]
            if not st.tokens:
                st.first_token_s = time.perf_counter() - t0
            st.tokens.append(tok)
            generated += 1
            if on_token is not None:
                on_token(st.req.rid, tok)

        def log_tick(t: float, at: int, chunks: int, chunk_tokens: int,
                     pages: tuple, expert_rows: int = 0) -> None:
            telemetry.LOG.append(telemetry.Tick(t, at, chunks, chunk_tokens,
                                                *pages, expert_rows))

        while pending or active:
            now = time.perf_counter() - t0
            while reached < len(arrivals) and arrivals[reached].arrival <= tick:
                due[arrivals[reached].rid] = now
                reached += 1
            chunks = chunk_tokens = 0
            queued = len(pending)
            with TraceAnnotation("serve.admit", tick=tick):
                while pending and free and pending[0].arrival <= tick:
                    if self.paged and not self.pool.can_admit(
                            len(pending[0].prompt) + pending[0].gen):
                        break      # FIFO head waits for pages to free up
                    req = pending.popleft()
                    slot = free.pop()
                    st = _Slot(req=req, admitted_tick=tick,
                               due_s=due.pop(req.rid),
                               admitted_s=time.perf_counter() - t0)
                    active[slot] = st
                    if self.paged:
                        self._admit_paged(req, slot, st)
                    else:
                        first = self._admit(req, slot)
                        if first is not None:
                            emit(slot, first)
                            if len(st.tokens) >= req.gen:
                                finish(slot)
            if self.paged:
                # chunked prefill: one fixed-shape chunk per prefilling slot
                # per tick, interleaved with the decode tick below
                firsts = []        # (slot, first token on the device)
                with TraceAnnotation("serve.prefill", tick=tick):
                    for slot in list(active):
                        st = active[slot]
                        if st.state != "prefill":
                            continue
                        before = st.cursor
                        first = self._prefill_chunk_tick(slot, st)
                        chunks += 1
                        chunk_tokens += st.cursor - before
                        if first is not None:
                            firsts.append((slot, first))
                if firsts:
                    # one wait for all of the tick's chunks, kept apart
                    # from the host's own work as serve.sync is
                    with TraceAnnotation("serve.pick", tick=tick):
                        firsts = [(slot, int(f)) for slot, f in firsts]
                    with TraceAnnotation("serve.emit", tick=tick):
                        for slot, first in firsts:
                            self._tok[slot] = first
                            emit(slot, first)
                            st = active[slot]
                            if len(st.tokens) >= st.req.gen:
                                finish(slot)
            decoding = [s for s, st in active.items() if st.state == "decode"]
            if not decoding:
                if chunks or len(pending) < queued:
                    log_tick(time.perf_counter(), tick, chunks, chunk_tokens,
                             self._pages())
                if active:
                    tick += 1      # prefill-only tick still advances time
                else:
                    # nothing resident: fast-forward the virtual clock
                    tick = pending[0].arrival if pending else tick + 1
                continue
            with TraceAnnotation("serve.prepare", tick=tick):
                if self.paged:
                    # alloc-on-write: this tick's token lands at pos, so each
                    # decoding row's chain must cover pos+1 tokens (reserved
                    # at admission — ensure can't fail); refresh the tables
                    for slot in decoding:
                        st = active[slot]
                        self.pool.ensure(st.req.rid, int(self._pos[slot]) + 1)
                        self._tables[slot] = self.pool.table(st.req.rid,
                                                             self.n_pages)
                    args = (jnp.asarray(self._tok), self.cache,
                            jnp.asarray(self._pos), jnp.asarray(self._tables))
                else:
                    args = (jnp.asarray(self._tok), self.cache,
                            jnp.asarray(self._pos))
            with TraceAnnotation("serve.dispatch", tick=tick):
                if self.sampling:
                    nxt, self.cache = self._decode(self.params, *args,
                                                   self._next_key())
                else:
                    nxt, self.cache = self._decode(self.params, *args)
            with TraceAnnotation("serve.sync", tick=tick):
                # host sync = the stream point; the expert layers' counts
                # come back with the tokens
                nxt, held = jax.device_get((nxt, self._expert_rows()))
            t_sync, pages = time.perf_counter(), self._pages()
            expert_rows = sum(int(h[:, decoding].sum()) for h in held)
            tick += 1
            try:
                with TraceAnnotation("serve.emit", tick=tick - 1):
                    for slot in decoding:
                        if slot not in active:
                            continue
                        self._pos[slot] += 1
                        self._tok[slot] = nxt[slot]
                        emit(slot, int(nxt[slot]))
                        if len(active[slot].tokens) >= active[slot].req.gen:
                            finish(slot)
            finally:
                # logged even when a token callback ends the run
                log_tick(t_sync, tick - 1, chunks, chunk_tokens, pages,
                         expert_rows)
        jax.block_until_ready(self.cache)
        wall = time.perf_counter() - t0
        out = {
            "completions": done,
            "generated": generated,
            "ticks": tick,
            "wall_s": wall,
            "tok_s": generated / wall if wall > 0 else float("inf"),
        }
        if self.paged:
            out["pool"] = self.pool.report()
        return out


def make_requests(n: int, prompt_len: int, gen: int, vocab: int, *,
                  stagger: int = 0, seed: int = 1) -> List[Request]:
    """Uniform synthetic request stream: ``n`` requests of ``prompt_len``
    random prompt tokens, ``gen`` outputs, arriving ``stagger`` ticks apart."""
    rng = np.random.RandomState(seed)
    return [Request(rid=i,
                    prompt=rng.randint(0, vocab, (prompt_len,)).astype(np.int32),
                    gen=gen, arrival=i * stagger)
            for i in range(n)]
