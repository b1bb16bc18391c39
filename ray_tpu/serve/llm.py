"""Continuous-batching LLM inference engine for Serve.

The reference serves LLMs through external engines (vLLM-style servers
behind Serve deployments); this engine is native and TPU-shaped:

- **Static shapes everywhere.** One compiled prefill program per prompt
  bucket (power-of-two widths) and ONE compiled decode program for the
  whole slot batch, reused every tick — no recompilation as requests
  come and go.
- **Slot-based continuous batching.** The decode batch is a fixed set of
  `max_batch` slots; new requests prefill into a free slot mid-flight
  while other slots keep decoding (the continuous-batching idea:
  admission does not wait for the batch to drain). Each slot advances
  at its own position: its length and its row of the page table say
  where its next token is written and what it attends over.
- **Streaming.** `submit()` returns a handle whose iterator yields tokens
  as they are produced; `LLMDeployment` plugs that into Serve's
  generator-streaming path (`handle.options(stream=True)` / `?stream=1`).
- **The host in the chip's shadow.** Between a chunk's fetch and the next
  dispatch the loop does only what that dispatch needs. A chunk's tokens
  reach their streams, a stream's end with them, after the next chunk is
  queued; a request that finds a slot empty is admitted and its prefill
  queued behind the chunk that is on the chip (`_loop`).
- **Paged KV.** Slots share one pool of fixed-size KV pages per layer
  (vLLM block tables, TPU-shaped: the scalar-prefetch pallas kernel in
  ops/paged_attention.py attends over scattered pages; PageAllocator
  manages the free list host-side). HBM is bounded by `kv_pool_tokens`
  RESIDENT tokens, not max_len x slots — admission defers requests when
  the pool is dry and pages return to the free list the moment a stream
  completes. `page_size` is a size like `max_len`, which it divides.
- **One seam to the model** (`serve/llm_families.py`): what a sequence's
  state is (which parts are paged, which are fixed per slot), prefill ->
  state at each row's last token, one decode step over the state. The
  engine compiles the family's functions under its own program names
  and knows no architecture: a rotary GQA decoder (a pool a layer) and
  a hybrid of state-space, window and shared-cache layers (pages of one
  layer, rings, recurrent state) run through the same loop.
"""

from __future__ import annotations

import itertools
import logging
import queue
import threading
import time
from collections import deque
from dataclasses import dataclass, field

import numpy as np

from ray_tpu.models.generate import SamplingParams
from ray_tpu.serve.llm_families import family_of
from ray_tpu.util import tracing

logger = logging.getLogger(__name__)

# The decode chunk's per-slot arguments, each beside the engine's numpy
# mirror of it. The mirrors are the truth; the device keeps a copy that
# the chunk program advances itself, and the host sends a mirror again
# only after it wrote to it in a way the program did not (`_dirty`).
_MIRRORS = {"token": "_token", "pos": "_pos", "lens": "_lens",
            "tables": "_tables", "temps": "_temps", "top_ks": "_topks",
            "top_ps": "_topps", "chunk_no": "_chunk_no", "steps": "_steps"}
_CURSOR = ("token", "pos", "lens")
# What a slot changing hands (an admission, a finish) leaves stale.
_SLOT = _CURSOR + ("tables",)


@dataclass
class _Slot:
    request: "RequestHandle | None" = None
    generated: int = 0
    # Allocator key owning this slot's pages.
    seq_id: str = ""
    # Every token this stream has generated (including ones still queued
    # in the handle). A drain snapshot ships this so a resumed stream can
    # re-deliver exactly the tokens the consumer never received.
    history: list = field(default_factory=list)


@dataclass
class _Program:
    """One program the loop has put on the chip, from the loop to the
    watcher (`_watch`), which sees it end and writes its `chip.program`
    span: a decode chunk (one dispatch), or a prefill group (its prefill,
    its write into pages and slots and its sampling, queued back to back)."""

    seq: int            # the chip runs programs in this order
    kind: str           # "decode" / "prefill"
    out: object         # its last output, on the device
    queued_ns: int      # the loop's clock when its FIRST dispatch returned
    attrs: dict         # what the span says of it besides
    fetched_ns: int = 0     # the loop's clock when its own fetch returned


@dataclass
class _Flight:
    """A prefill dispatched behind a program that is on the chip, its
    first tokens not fetched yet."""

    requests: list      # (slot, seq_id, prompt, handle), pages reserved
    width: int          # rows of the program
    rows: list          # each request's row of the page table
    program: _Program   # `out`: first tokens (and counts), on the device
    held: int           # bytes of the program's outputs, see `_next_fits`


class _Prefilled:
    """Admission payload for a request whose prefill ran in ANOTHER
    engine (the disaggregated prefill pool, or a resume after a drain
    evacuation): the per-layer KV prefix plus the decode cursor."""

    __slots__ = ("kv_layers", "token", "prompt_len", "lens", "generated",
                 "history", "emit_first")

    def __init__(self, kv_layers, token, prompt_len, lens, generated,
                 history, emit_first):
        self.kv_layers = kv_layers  # [(k, v)] per layer, (Hkv, L, D)
        self.token = int(token)      # next decode input (last sampled)
        self.prompt_len = int(prompt_len)
        self.lens = int(lens)        # valid KV entries
        self.generated = int(generated)
        self.history = list(history or [])
        self.emit_first = bool(emit_first)


class RequestHandle:
    """Client-side stream of generated tokens for one request.

    The stream is BOUNDED (`max_buffered`): a consumer that stops
    draining while decode keeps producing parks the producing slot
    (backpressure) instead of growing host memory without limit.

    The engine's loop BOOKS a token (`_offer`) the moment it has decided
    it, and hands what it booked to the consumer later, whole and with
    the stream's end if there is one (`_hand_over`): one turn of the
    stream's lock for a chunk of tokens, taken when the loop has queued
    the chip's next program and not while the chip waits for it. A booked
    token takes its place in the bound at once, so what is booked always
    fits when it is handed over."""

    _rids = itertools.count(1)

    def __init__(self, prompt_len: int, sampling: SamplingParams,
                 max_buffered: int = 256, tag: str = ""):
        self.prompt_len = prompt_len
        self.sampling = sampling
        self.tag = tag  # router-visible stream key (disagg resume)
        # Process-unique: what the spans of this request share
        # (`request.queue`, `request.first_token`, `engine.admit`'s `rids`).
        self.rid = next(RequestHandle._rids)
        self._max_buffered = max(1, max_buffered)
        # Booked by the engine's loop (that thread alone), in order.
        self._booked: list = []
        # Handed over and not yet taken; `_wake` guards it and the end.
        self._handed: deque = deque()
        self._ended = False
        self._wake = threading.Condition(threading.Lock())
        self._submit_ns = time.monotonic_ns()
        self._submit_ts = self._submit_ns / 1e9
        self.error: Exception | None = None

    def _offer(self, tok: int) -> bool:
        """Book one token; False = consumer backlog full. The engine
        parks the slot on False: it must never block its loop on a slow
        consumer."""
        if self.room() <= 0:
            return False
        self._booked.append(tok)
        return True

    def _hand_over(self, end: bool = False,
                   error: Exception | None = None) -> int:
        """What is booked to the consumer, and the stream's end with it,
        in one turn of the lock; the consumer wakes once, and at its end
        returns at once. Returns the tokens handed over."""
        with self._wake:
            n = len(self._booked)
            self._handed.extend(self._booked)
            self._booked.clear()
            if error is not None and self.error is None:
                self.error = error
            self._ended = self._ended or end or error is not None
            self._wake.notify_all()
        return n

    def _finish(self, error: Exception | None = None) -> None:
        self._hand_over(end=True, error=error)

    def backlog_full(self) -> bool:
        return self.room() <= 0

    def room(self) -> int:
        """Tokens the stream takes now without parking. What is booked
        and not yet handed over counts as taken: the consumer cannot
        drain it, so between two hand-overs room only shrinks, and a
        slot is never given steps whose tokens would not fit."""
        return self._max_buffered - len(self._handed) - len(self._booked)

    def __iter__(self):
        while True:
            with self._wake:
                while not self._handed:
                    if self._ended:
                        if self.error is not None:
                            raise self.error
                        return
                    self._wake.wait()
                tok = self._handed.popleft()
            yield tok

    def tokens(self) -> list[int]:
        """Block until completion; all tokens as a list."""
        return list(self)


class LLMEngine:
    """Slot-based continuous-batching engine over any model family that
    `serve/llm_families.py` knows (`cfg`: that family's configuration)."""

    def __init__(self, cfg, params, *, max_batch: int = 4,
                 max_len: int = 1024, decode_chunk: int = 8,
                 rng_seed: int = 0, page_size: int = 64,
                 kv_pool_tokens: int = 0, stream_buffer: int = 256):
        import jax
        import jax.numpy as jnp

        self.cfg = cfg
        self.params = params
        self.max_batch = max_batch
        self.max_len = max_len
        # Per-stream token-queue bound: a non-draining consumer parks its
        # slot (backpressure) once this many tokens are buffered.
        self._stream_buffer = max(1, stream_buffer)
        # Prefill→decode KV handoff rides the device object plane
        # (_private/device_objects.py): the freshly prefilled per-request
        # KV is pinned, resolved by decode over the cheapest route
        # (same-process → zero-copy handover of the live arrays), and
        # unpinned — pinned-KV bytes and handoff counts are observable
        # through the plane's gauges. Fails open: any plane error falls
        # back to the direct in-memory handoff, counted in
        # handoff_fallbacks.
        self.handoff_fallbacks = 0
        # Cumulative over decode dispatches: the table pages
        # the kernel had to visit, and the pages the tables hold. Counters,
        # so that a reader takes the share over its own window (and leaves
        # warm-up out) by difference.
        self.paged_pages_live = 0
        self.paged_pages_table = 0
        # Slots whose fixed per-slot state (rings, recurrent state) an
        # admission replaced with what its own prefill computed from zero.
        self.state_slots_reset = 0
        # Live slots summed over the decode steps dispatched: with the
        # family's `state_bytes_per_slot`, what the fixed state cost a
        # window (each live slot's state is read and written once a step).
        self.state_slot_steps = 0
        # Decode chunks dispatched, and those of them for which the host
        # sent none of the chunk's resident arguments again (a pass that
        # follows no admission, finish or park).
        self.decode_passes = 0
        self.decode_passes_clean = 0
        # Requests given a slot and pages, and those of them whose prefill
        # was dispatched behind a decode chunk that was on the chip.
        self.admissions = 0
        self.admissions_under_chunk = 0
        # Tokens a KV page holds: admission is bounded by POOL pages
        # (resident tokens), not slot count x max_len.
        if page_size <= 0:
            raise ValueError(
                f"page_size={page_size} must be positive: the engine "
                "keeps every stream's KV in pages")
        if max_len % page_size:
            raise ValueError(
                f"max_len={max_len} must be a multiple of "
                f"page_size={page_size}")
        self.page_size = page_size
        # Steps per compiled decode call: one host sync per CHUNK, not per
        # token (dispatch/fetch latency dominates single-token decode).
        # Admission waits at most one chunk; tokens stream with chunk
        # granularity.
        self.decode_chunk = max(1, decode_chunk)
        # What the model tells the engine (serve/llm_families.py).
        self.family = family = family_of(cfg, max_len)
        self.model = family.model
        # Small integers a family's device programs count beside their
        # logits (`llm_families.py`: `step_counters`, `prefill_counters`):
        # fetched with the tokens, put on the spans, kept here in all.
        self._step_counters = tuple(getattr(family, "step_counters", ()))
        self._prefill_counters = tuple(
            getattr(family, "prefill_counters", ()))
        self.family_counters = {
            name: 0 for name, _ in
            self._step_counters + self._prefill_counters}
        self._jax, self._jnp = jax, jnp
        self._rng = jax.random.PRNGKey(rng_seed)
        # Decode draws from `fold_in(fold_in(base, chunk), step)`: the
        # base stays on the device, the chunk's number rides in the carry.
        self._rng_base = self._rng

        # ---- compiled programs ------------------------------------------
        # The family's functions under the engine's own program names (a
        # profile is read by them): `prefill_one` and `prefill_many` are
        # one function at two widths (called for no family that prefills
        # from the host: `_dispatch_prefill`).

        import functools

        @jax.jit
        def prefill_one(params, tokens, last_idx):
            # tokens: (1, bucket) right-padded; last_idx: (1,)
            return family.prefill(params, tokens, last_idx)

        V = cfg.vocab_size

        @jax.named_scope("sample")
        def _sample(logits, temps, top_ks, top_ps, rng):
            # Per-slot temperature / top-k / top-p, fully vectorized
            # (matches models/generate.sample_logits semantics per row;
            # top_ks==0 and top_ps==1 disable the truncations). A batch
            # whose rows are all greedy is an argmax and nothing else:
            # the sort over the whole vocabulary was 20% of the device's
            # time at 32 x 200,064 (my chip run, PR 28), 3.4% at 32,768.
            greedy = jnp.argmax(logits, axis=-1)

            def sampled():
                scaled = logits / jnp.maximum(temps, 1e-6)[:, None]
                sorted_l = jnp.sort(scaled, axis=-1)[:, ::-1]
                k_idx = jnp.clip(jnp.where(top_ks > 0, top_ks, V) - 1, 0,
                                 V - 1)
                kth = jnp.take_along_axis(sorted_l, k_idx[:, None], axis=-1)
                cut = jnp.where(scaled < kth, -1e30, scaled)
                probs = jax.nn.softmax(sorted_l, axis=-1)
                cum = jnp.cumsum(probs, axis=-1)
                cut_idx = jnp.clip(jnp.sum(cum < top_ps[:, None], axis=-1),
                                   0, V - 1)
                cutoff = jnp.take_along_axis(sorted_l, cut_idx[:, None],
                                             axis=-1)
                cut = jnp.where(cut < cutoff, -1e30, cut)
                drawn = jax.random.categorical(rng, cut, axis=-1)
                return jnp.where(temps <= 0.0, greedy, drawn)

            return jax.lax.cond(jnp.all(temps <= 0.0), lambda: greedy,
                                sampled)

        self._sample = jax.jit(_sample)
        # A prefill's counts leave the device WITH its first tokens.
        self._sample_counted = jax.jit(
            lambda counts, *args: jnp.concatenate([_sample(*args), counts]))
        self._prefill_one = prefill_one

        K = self.decode_chunk
        # Overshoot margin: a chunk of K steps may run up to K-1
        # tokens past a stream's max_new before the host notices eos.
        pool_tokens = kv_pool_tokens or max_batch * (max_len + K)
        self._np_pages = -(-(max_len + K) // page_size)  # table width
        self._num_pages = -(-pool_tokens // page_size) + 1  # + dummy
        self._init_paged_state()  # allocator, `_pools`, `_tables`

        def decode_chunk_paged(params, token, pos, pools, tables, lens,
                               temps, top_ks, top_ps, rng_base, chunk_no,
                               steps=None):
            # K decode steps in one program (lax.scan): sampling happens
            # in-device, so only the (K, B) token block crosses to host.
            # The cursor (`token`, `pos`, `lens`) and the chunk's number
            # come back as the next chunk's own: the engine hands them in
            # again as they are unless the host moved a cursor meanwhile.
            # `steps` (B,), where the family's state cannot be rewound:
            # a slot advances that many steps of the chunk and is held
            # still after them (a parked or an empty slot: 0). Without
            # it a slot that holds no stream (`lens` 0: a live one has
            # its prompt) is held still, or its length would creep up a
            # chunk at a time and the kernel's work with it.
            chunk_rng = jax.random.fold_in(rng_base, chunk_no)
            occupied = lens > 0

            def body(carry, i):
                token, pos, pools, lens = carry
                live = None if steps is None else i < steps
                logits, pools2, *counts = family.decode(
                    params, token, pos, pools, tables, lens, live)
                tok = _sample(logits, temps, top_ks, top_ps,
                              jax.random.fold_in(chunk_rng, i))
                moves = occupied if live is None else live
                # (a family that counts: its step's counts as further
                # columns of the step's row of tokens, one fetch for both)
                return (jnp.where(moves, tok, token), pos + moves, pools2,
                        lens + moves), \
                    jnp.concatenate([tok, *counts]) if counts else tok

            (token, pos, pools, lens), toks = jax.lax.scan(
                body, (token, pos, pools, lens), jnp.arange(K))
            # toks: (K, B), or (K, B + counters)
            return toks, pools, token, pos, lens, chunk_no + 1

        # Donating the state makes each chunk update it in place.
        self._decode_chunk_paged = jax.jit(decode_chunk_paged,
                                           donate_argnums=(3,))

        # ---- batched prefill admission ----------------------------------
        # Sequential slot prefills dominate end-to-end serving at large
        # batch (each is a full program dispatch). When several
        # same-bucket requests are pending, ONE (W, bucket) prefill serves
        # all of them. W is FIXED for a bucket (padding with rows that
        # scatter into the dummy page) so exactly one extra program per
        # bucket compiles, regardless of arrival pattern; the family says
        # how many rows a bucket takes, at most this many.
        self._batch_prefill_width = family.prefill_width(
            page_size, max_batch)

        @jax.jit
        def prefill_many(params, tokens, last_idx):
            # tokens: (W, bucket) right-padded; last_idx: (W,) index
            # of each row's last prompt token. Returns the last-token
            # logits row per sequence and the rows' fresh state.
            return family.prefill(params, tokens, last_idx)

        self._prefill_many = prefill_many
        # ... or the family's own loop of dispatches over a prompt's blocks,
        # in place of both (`llm_families.py`: `prefill_from_host`).
        self._prefill_from_host = getattr(family, "prefill_from_host", None)

        # The rows' fresh state into the engine's: paged parts to
        # `page_ids` (W, n), fixed parts to `slots` (W,).
        self._write_prompt_pages = functools.partial(
            jax.jit, donate_argnums=(0,))(family.write_prompt)
        self._deferred: list = []  # pool-dry admissions, FIFO retry

        # ---- engine state (host-managed; `_pools` and `_tables` above) ---

        self._lens = np.zeros(max_batch, np.int32)
        self._token = np.zeros(max_batch, np.int32)
        self._pos = np.zeros(max_batch, np.int32)
        self._temps = np.zeros(max_batch, np.float32)
        self._topks = np.zeros(max_batch, np.int32)
        self._topps = np.ones(max_batch, np.float32)
        self._chunk_no = np.zeros((), np.int32)
        self._slots = [_Slot() for _ in range(max_batch)]
        # Submitted and not yet taken by the loop, first come first; under
        # `_arrivals`, which wakes the loop for an arrival and, while it
        # waits behind a program on the chip, for that program's end
        # (`_chip_done`, the watcher thread's word: the number of the last
        # program that has ended).
        self._pending: deque = deque()
        self._arrivals = threading.Condition(threading.Lock())
        self._chip_done = 0
        # Every program the loop dispatches, in the chip's order (`_Program`).
        self._programs = itertools.count(1)
        self._watched: queue.SimpleQueue = queue.SimpleQueue()
        # Streams with tokens booked and not yet handed over -> whether
        # the stream ended with them (`_hand_off`).
        self._booked: dict = {}
        # Prefills dispatched behind a program on the chip (`_Flight`), and
        # the bytes of a prefill's outputs by (width, bucket), as seen.
        self._in_flight: list = []
        self._prefill_bytes: dict = {}
        self._stop = threading.Event()
        # Drain quiesce handshake: _quiesce asks the loop to pause at a
        # tick boundary; the loop acks via _quiet, after which slot/KV
        # state is stable for snapshot_active_streams().
        self._quiesce = threading.Event()
        self._quiet = threading.Event()
        # Named metrics for the per-pool autoscaler + bench surface.
        self._ttft = deque(maxlen=256)  # seconds, submit -> first token
        self._parked_events = 0  # backpressure: offers rejected (q full)
        self._thread = threading.Thread(target=self._loop, daemon=True,
                                        name="llm-engine")
        self._thread.start()
        self._watcher = threading.Thread(target=self._watch, daemon=True,
                                         name="llm-engine-watch")
        self._watcher.start()

    # ---- public API ------------------------------------------------------

    def submit(self, prompt_tokens, sampling: SamplingParams | None = None,
               tag: str = "") -> RequestHandle:
        prompt = np.asarray(prompt_tokens, np.int32).reshape(-1)
        sp = sampling or SamplingParams()
        if len(prompt) + sp.max_new_tokens > self.max_len:
            raise ValueError(
                f"prompt({len(prompt)}) + max_new_tokens({sp.max_new_tokens})"
                f" exceeds engine max_len={self.max_len}")
        need = self._alloc.pages_needed(
            len(prompt) + sp.max_new_tokens + self.decode_chunk)
        if need > self._alloc.num_pages - 1:  # -1: dummy page
            raise ValueError(
                f"request needs {need} KV pages but the pool holds "
                f"{self._alloc.num_pages - 1}; raise kv_pool_tokens")
        handle = RequestHandle(len(prompt), sp,
                               max_buffered=self._stream_buffer, tag=tag)
        self._arrive(prompt, handle)
        return handle

    def _arrive(self, what, handle: RequestHandle) -> None:
        with self._arrivals:
            self._pending.append((what, handle))
            self._arrivals.notify()

    def submit_prefilled(self, pack: _Prefilled,
                         sampling: SamplingParams | None = None,
                         tag: str = "") -> RequestHandle:
        """Admit a request whose prefill ran elsewhere (disaggregated
        prefill pool, or a drain-evacuated stream being resumed): the
        KV prefix lands in the slot's pages and decoding continues from
        `pack.token` without re-running prefill here."""
        self._require_portable_kv("submit_prefilled")
        sp = sampling or SamplingParams()
        budget = sp.max_new_tokens - pack.generated
        if budget <= 0:
            raise ValueError("prefilled request has no decode budget left")
        if pack.lens + budget > self.max_len:
            raise ValueError(
                f"kv_len({pack.lens}) + remaining({budget}) exceeds "
                f"engine max_len={self.max_len}")
        handle = RequestHandle(pack.prompt_len, sp,
                               max_buffered=self._stream_buffer, tag=tag)
        self._arrive(pack, handle)
        return handle

    def _require_portable_kv(self, what: str) -> None:
        if not self.family.portable_kv:
            raise NotImplementedError(
                f"{what}: a {type(self.cfg).__name__} stream's state is "
                "pages and fixed per-slot state (rings, recurrent state), "
                "not a per-layer KV prefix; it is prefilled where it "
                "decodes")

    def generate(self, prompt_tokens,
                 sampling: SamplingParams | None = None) -> list[int]:
        return self.submit(prompt_tokens, sampling).tokens()

    def num_active(self) -> int:
        return sum(1 for s in self._slots if s.request is not None)

    def tokens_in_flight(self) -> int:
        """Remaining decode budget across active streams — the decode
        pool's autoscaling signal."""
        total = 0
        for st in self._slots:
            h = st.request
            if h is not None:
                total += max(0, h.sampling.max_new_tokens - st.generated)
        return total

    def queue_depth(self) -> int:
        """Requests that hold no slot yet, or hold one and await their
        first token behind the chunk on the chip."""
        return len(self._pending) + len(self._deferred) \
            + sum(len(f.requests) for f in list(self._in_flight))

    def report_metrics(self) -> dict:
        ttft = sorted(self._ttft)
        pick = lambda q: ttft[min(len(ttft) - 1,  # noqa: E731
                                  int(q * len(ttft)))] if ttft else 0.0
        return {
            "queue_depth": float(self.queue_depth()),
            "tokens_in_flight": float(self.tokens_in_flight()),
            "active_streams": float(self.num_active()),
            "parked_events": float(self._parked_events),
            "handoff_fallbacks": float(self.handoff_fallbacks),
            "paged_pages_live": float(self.paged_pages_live),
            "paged_pages_table": float(self.paged_pages_table),
            "paged_live_share": (self.paged_pages_live
                                 / max(1, self.paged_pages_table)),
            # Fixed per-slot state: slots an admission has reset so far.
            "state_slots_reset": float(self.state_slots_reset),
            "state_bytes_per_slot": float(self.family.state_bytes_per_slot),
            "state_slot_steps": float(self.state_slot_steps),
            "decode_passes": float(self.decode_passes),
            "decode_passes_clean": float(self.decode_passes_clean),
            "admissions": float(self.admissions),
            "admissions_under_chunk": float(self.admissions_under_chunk),
            # what the family's programs counted (e.g. experts touched)
            **{k: float(v) for k, v in self.family_counters.items()},
            "ttft_p50_ms": pick(0.5) * 1e3,
            "ttft_p99_ms": pick(0.99) * 1e3,
        }

    def quiesce_for_drain(self, timeout: float = 10.0) -> bool:
        """Pause the loop at a tick boundary so slot/KV state is stable
        for snapshot_active_streams(). Returns True once the loop acked."""
        self._quiesce.set()
        return self._quiet.wait(timeout)

    def resume(self) -> None:
        self._quiesce.clear()
        self._quiet.clear()

    def snapshot_active_streams(self) -> dict:
        """Host-side snapshot of every decoding stream — caller must
        quiesce first. Keyed by the handle's tag; each value holds the
        trimmed per-layer KV (numpy) and the full decode cursor, enough
        to rebuild the stream via submit_prefilled on another replica."""
        self._require_portable_kv("snapshot_active_streams")
        out: dict = {}
        ps = self.page_size
        for i, st in enumerate(self._slots):
            h = st.request
            if h is None:
                continue
            # The stream's pages, by its table row, back into a per-layer
            # prefix of its L tokens.
            L = int(self._lens[i])
            n = -(-L // ps)
            row = self._tables[i][:n]
            kv = []
            for kp, vp in self._pools:
                Hkv, D = kp.shape[1], kp.shape[3]
                k = np.asarray(kp[row]).transpose(1, 0, 2, 3).reshape(
                    Hkv, n * ps, D)[:, :L]
                v = np.asarray(vp[row]).transpose(1, 0, 2, 3).reshape(
                    Hkv, n * ps, D)[:, :L]
                kv.append((k, v))
            sp = h.sampling
            out[h.tag or f"slot{i}"] = {
                "kv": kv,
                "prompt_len": int(h.prompt_len),
                "lens": L,
                "token": int(self._token[i]),
                "generated": int(st.generated),
                "history": list(st.history),
                "sampling": {"max_new_tokens": sp.max_new_tokens,
                             "temperature": sp.temperature,
                             "top_k": sp.top_k, "top_p": sp.top_p,
                             "eos_token": sp.eos_token},
            }
        return out

    def shutdown(self):
        self._stop.set()
        self._thread.join(5.0)
        # (the watcher first writes the span of every program dispatched)
        self._watched.put(None)
        self._watcher.join(5.0)
        self._fail_all(RuntimeError("engine shut down"))

    def _fail_all(self, err: Exception):
        """Unblock every waiter: active slots, admissions whose first
        tokens are in flight (in no slot yet; their pages go with their
        slots'), deferred and queued requests. A stream that had ended
        before ends as it did."""
        self._hand_off()
        for i, st in enumerate(self._slots):
            if st.request is not None:
                st.request._finish(err)
                st.request = None
            self._free_slot_pages(i)
        for flight in self._in_flight:
            for _slot, _seq_id, _prompt, handle in flight.requests:
                handle._finish(err)
        self._in_flight.clear()
        for _prompt, handle in self._deferred:
            handle._finish(err)
        self._deferred.clear()
        with self._arrivals:
            queued = list(self._pending)
            self._pending.clear()
        for _prompt, handle in queued:
            handle._finish(err)

    # ---- engine loop -----------------------------------------------------

    def _bucket(self, n: int) -> int:
        b = 16
        while b < n:
            b *= 2
        return min(b, self.max_len)

    def _device_handoff(self, kv):
        """Hand the prefill KV cache to decode as a device object:
        same-process resolution returns the SAME live arrays (zero copy)
        while ticking the plane's pinned-HBM gauge and in_process
        counter — the serve hot path's first device-plane consumer."""
        try:
            from ray_tpu._private import device_objects

            return device_objects.local_handoff("llm-prefill-kv", kv)
        except Exception:
            self.handoff_fallbacks += 1
            logger.warning("device-plane KV handoff failed; handing the "
                           "arrays over directly", exc_info=True)
            return kv

    def _commit_token(self, slot: int, handle: RequestHandle, tok: int,
                      prompt_len: int):
        """Prefill->decode handoff: commit an already-sampled first token
        + per-slot decode state (admission samples a whole group in one
        dispatch)."""
        self._commit_cursor(slot, handle, tok, prompt_len)
        st = self._slots[slot]
        st.request = handle
        st.generated = 0
        st.history = []
        self._first_token(slot, handle, tok)

    def _commit_cursor(self, slot: int, handle: RequestHandle, tok: int,
                       lens: int):
        """The slot's decode cursor and sampling into the host mirrors
        (its table row is already written). The device's copies of the
        cursor and the tables are stale now; of a sampling array only
        where the slot's value changed, so an all-greedy run never sends
        those again."""
        sp = handle.sampling
        self._lens[slot] = lens
        self._pos[slot] = lens
        self._token[slot] = tok
        self._dirty.update(_SLOT)
        for name, value in (("temps", sp.temperature), ("top_ks", sp.top_k),
                            ("top_ps", sp.top_p)):
            mirror = getattr(self, _MIRRORS[name])
            was = mirror[slot]
            mirror[slot] = value
            if mirror[slot] != was:
                self._dirty.add(name)

    def _first_token(self, slot: int, handle: RequestHandle, tok: int):
        self._ttft.append(time.monotonic() - handle._submit_ts)
        self._emit(slot, tok)
        tracing.record_span("request.first_token", handle._submit_ns,
                            rid=handle.rid)

    def _commit_prefilled(self, slot: int, handle: RequestHandle,
                          pack: _Prefilled):
        """Commit decode state for an externally prefilled stream. A
        fresh handoff (emit_first=True) behaves like _commit_token with
        the prefill pool's sampled first token; a resume carries the
        full history/cursor and emits nothing until decode advances."""
        self._commit_cursor(slot, handle, pack.token, pack.lens)
        st = self._slots[slot]
        st.request = handle
        st.generated = pack.generated
        st.history = list(pack.history)
        if pack.emit_first:
            self._first_token(slot, handle, pack.token)

    def _admit_prefilled_paged(self, slot: int, seq_id: str,
                               pack: _Prefilled, handle: RequestHandle):
        """Scatter an external KV prefix into this sequence's reserved
        pages. The prefix is padded up to the engine bucket (a page
        multiple) so the page writer compiles one variant per bucket,
        not one per arbitrary kv length; pad rows scatter into the dummy
        page, never a page a live sequence owns."""
        jnp = self._jnp
        ps = self.page_size
        Lb = -(-max(self._bucket(pack.lens), ps) // ps) * ps
        n_real = -(-pack.lens // ps)
        row = self._alloc.table_row(seq_id, self._np_pages)
        page_ids = np.full(Lb // ps, self._dummy_page, np.int32)
        page_ids[:n_real] = row[:n_real]
        kv_pad = []
        for k1, v1 in pack.kv_layers:
            k1 = np.asarray(k1)[:, :pack.lens]
            v1 = np.asarray(v1)[:, :pack.lens]
            Hkv, L, D = k1.shape
            kp = np.zeros((Hkv, Lb, D), k1.dtype)
            vp = np.zeros((Hkv, Lb, D), v1.dtype)
            kp[:, :L] = k1
            vp[:, :L] = v1
            kv_pad.append((jnp.asarray(kp[None], self.cfg.dtype),
                           jnp.asarray(vp[None], self.cfg.dtype)))
        self._pools = self._write_prompt_pages(
            self._pools, kv_pad, jnp.asarray([slot], jnp.int32),
            jnp.asarray(page_ids[None]))
        self._tables[slot] = row
        self._commit_prefilled(slot, handle, pack)

    def _reserve_paged(self, slot: int, prompt: np.ndarray,
                       handle: RequestHandle) -> str:
        """Reserve pages for the stream's WHOLE lifetime (prompt +
        max_new + chunk overshoot) up front, so decode can never fail
        mid-stream on an empty pool; MemoryError here defers the request
        instead (admission control by resident tokens)."""
        sp = handle.sampling
        st = self._slots[slot]
        seq_id = f"slot{slot}-{id(handle):x}"
        if isinstance(prompt, _Prefilled):
            need = prompt.lens + (sp.max_new_tokens - prompt.generated) \
                + self.decode_chunk
        else:
            need = len(prompt) + sp.max_new_tokens + self.decode_chunk
        self._alloc.allocate(seq_id, need)  # MemoryError -> caller defers
        st.seq_id = seq_id
        return seq_id

    def _admit_paged_group(self, cands: list, under_chunk: bool) -> None:
        """Prefill reserved candidates, batching same-bucket requests
        through the fixed-width prefill_many program (one dispatch for
        up to _batch_prefill_width streams). Singleton groups keep the
        single-sequence program. cands: (slot, seq_id, prompt, handle)
        with pages already reserved. `under_chunk`: a program is on the
        chip, so each group is only dispatched behind it; its first
        tokens are fetched and committed after the chunk's walk
        (`_finish_prefill`, from `_in_flight`)."""
        self.admissions += len(cands)
        self.admissions_under_chunk += under_chunk * len(cands)
        # Externally prefilled streams skip the prefill programs entirely:
        # their KV prefix scatters straight into the reserved pages.
        for slot, seq_id, pack, handle in \
                [c for c in cands if isinstance(c[2], _Prefilled)]:
            try:
                self._admit_prefilled_paged(slot, seq_id, pack, handle)
            except BaseException as e:
                self._free_slot_pages(slot)
                handle._finish(e)
        cands = [c for c in cands if not isinstance(c[2], _Prefilled)]
        groups: dict = {}
        for c in cands:
            bucket = max(self._bucket(len(c[2])), self.page_size)
            groups.setdefault(bucket, []).append(c)
        for bucket, group in groups.items():
            width = self.family.prefill_width(bucket, self.max_batch)
            while group:
                chunk = group[:width]
                group = group[len(chunk):]
                # A request alone keeps the single-sequence program.
                W = 1 if len(chunk) == 1 else width
                lengths = [len(c[2]) for c in chunk]
                what = dict(bucket=bucket, rows=len(chunk), width=W,
                            computed=self._prefill_computed(bucket, lengths))
                if self._prefill_from_host is not None:
                    what["blocks"] = self.family.prompt_blocks(lengths)
                with tracing.span("engine.prefill", **what):
                    flight = self._dispatch_prefill(chunk, what)
                    if flight is None:
                        continue
                    if under_chunk:
                        self._in_flight.append(flight)
                    else:
                        self._finish_prefill(flight)

    def _prefill_computed(self, bucket: int, lengths: list) -> int:
        """Positions a prefill program computes for a group: every row's
        whole bucket, or what the family says where its program stops at
        the prompts' end (`prefill_computed`)."""
        stops = getattr(self.family, "prefill_computed", None)
        return stops(bucket, lengths) if stops else bucket * len(lengths)

    def _count(self, counters: tuple, values: np.ndarray) -> dict:
        """What a family's program counted, by name: `values` (..., n)
        reduced over its leading axes as each counter says ("sum" or
        "max"), and taken into `family_counters`."""
        out = {}
        for j, (name, how) in enumerate(counters):
            out[name] = got = int(getattr(np, how)(values[..., j]))
            kept = self.family_counters[name]
            self.family_counters[name] = max(kept, got) if how == "max" \
                else kept + got
        return out

    def _fail_group(self, chunk: list, err: BaseException) -> None:
        """A device-level failure sinks a prefill's whole dispatch: fail
        every member and return their pages."""
        for slot, _seq_id, _prompt, handle in chunk:
            self._free_slot_pages(slot)
            handle._finish(err)

    def _dispatch_prefill(self, chunk: list, what: dict):
        """One prefill dispatch for `chunk` (at most `what["width"]`
        requests of the bucket `what["bucket"]`, pages reserved): the
        prefill (one program, or where the family prefills from the host,
        `prefill_from_host`, its own programs queued back to back), the
        rows' state into pages and slots and one sampling dispatch, nothing
        fetched; together they are ONE program of the chip's ledger
        (`_on_chip`), which says of it what `what` says of its
        `engine.prefill`. Returns what `_finish_prefill` takes, or None
        where the dispatch failed (and its requests with it)."""
        jnp = self._jnp
        bucket, W = what["bucket"], what["width"]
        npages_row = self.family.prompt_pages(bucket, self.page_size)
        tokens = np.zeros((W, bucket), np.int32)
        last_idx = np.zeros((W,), np.int32)
        page_rows = np.full((W, npages_row), self._dummy_page, np.int32)
        # A padding row's fixed state goes nowhere (dropped), its pages
        # to the dummy page.
        slots = np.full((W,), self.max_batch, np.int32)
        # Sampling params padded to the FIXED width W: a partial
        # group must not compile its own (n, V) _sample variant.
        temps = np.zeros(W, np.float32)
        topks = np.zeros(W, np.int32)
        topps = np.ones(W, np.float32)
        rows = []
        for r, (slot, seq_id, prompt, handle) in enumerate(chunk):
            tokens[r, : len(prompt)] = prompt
            last_idx[r] = len(prompt) - 1
            slots[r] = slot
            row = self._alloc.table_row(seq_id, self._np_pages)
            rows.append(row)
            npp = self._alloc.pages_needed(len(prompt))
            page_rows[r, :npp] = row[:npp]
            temps[r] = handle.sampling.temperature
            topks[r] = handle.sampling.top_k
            topps[r] = handle.sampling.top_p
        try:
            if self._prefill_from_host is not None:
                # The family's own dispatches, on this thread: a block of
                # the prompts' positions a program (`blocks` of them). The
                # first, a state of zeros, is queued within the call's first
                # half millisecond: the chip is at work from here.
                queued_ns = time.monotonic_ns()
                last_logits, fresh, *counts = self._prefill_from_host(
                    self.params, tokens, last_idx)
            else:
                prefill = self._prefill_one if W == 1 \
                    else self._prefill_many
                last_logits, fresh, *counts = prefill(
                    self.params, jnp.asarray(tokens), jnp.asarray(last_idx))
                # (the chip, where it had nothing queued, is at work from
                # here)
                queued_ns = time.monotonic_ns()
            fresh = self._device_handoff(fresh)
            self._pools = self._write_prompt_pages(
                self._pools, fresh, jnp.asarray(slots),
                jnp.asarray(page_rows))
            # ONE sampling dispatch for the whole group; it must be
            # covered too, or a transient device error kills the engine
            # thread and strands every waiter (no sentinel ever lands).
            # Greedy stays bit-equal whatever the group: argmax ignores
            # the rng mapping.
            self._rng, srng = self._jax.random.split(self._rng)
            toks = self._sample_counted(*counts, last_logits, temps, topks,
                                        topps, srng) if counts else \
                self._sample(last_logits, temps, topks, topps, srng)
        except BaseException as e:
            self._fail_group(chunk, e)
            return None
        program = self._on_chip(
            "prefill", toks, queued_ns, **what,
            prompt_tokens=sum(len(c[2]) for c in chunk),
            rids=[c[3].rid for c in chunk])
        held = self._prefill_bytes.get((W, bucket))
        if held is None:
            held = self._prefill_bytes[W, bucket] = sum(
                x.nbytes for x in self._jax.tree_util.tree_leaves(
                    (last_logits, fresh)))
        return _Flight(chunk, W, rows, program, held)

    def _finish_prefill(self, flight: _Flight, free=()) -> None:
        """A dispatched prefill's first tokens (and what the family's
        program counted, `prefill_counters`, which ride on the fetch's
        span): ONE host sync for the whole group, then the host-side
        commit. The chip is at work on the prefill, so first what is
        booked is handed over and arrivals are served into `free`."""
        chunk, W, rows = flight.requests, flight.width, flight.rows
        program = flight.program
        self._hand_off()
        self._admit_behind(program, free)
        wait = tracing.span("engine.prefill.wait").begin()
        try:
            toks = np.asarray(program.out)
        except BaseException as e:
            wait.end()
            self._fail_group(chunk, e)
            return
        program.fetched_ns = time.monotonic_ns()
        wait.end(**self._count(self._prefill_counters, toks[W:]))
        # Host-only from here: no device call can strand waiters.
        if not self.family.rewinds:
            self.state_slots_reset += len(chunk)
        for r, (slot, seq_id, prompt, handle) in enumerate(chunk):
            self._tables[slot] = rows[r]
            self._commit_token(slot, handle, int(toks[r]), len(prompt))

    def _init_paged_state(self):
        """(Re)build the page pool: allocator + dummy page + the family's
        zeroed state + tables. Shared by __init__ and the
        decode-failure recovery path so the two can never drift."""
        from ray_tpu.ops.paged_attention import PageAllocator

        self._alloc = PageAllocator(self._num_pages, self.page_size)
        # Dummy page: inactive slots' garbage writes and table padding
        # land here, never in a page a live sequence owns.
        self._dummy_page = self._alloc.allocate("__dummy__", 1)[0]
        # The engine's whole decode state: what is paged and, where the
        # family has it, what is fixed per slot (`_pools` for a family
        # with nothing fixed: the list of its layers' pools).
        self._pools = self.family.init_state(
            self.max_batch, self._num_pages, self.page_size)
        self._tables = np.full((self.max_batch, self._np_pages),
                               self._dummy_page, np.int32)
        # Nothing of the decode chunk's arguments is on the device (yet,
        # or any more: a failed chunk may have taken its carry with it).
        self._dev: dict = {}
        self._dirty = set(_MIRRORS) - {"steps"}
        # What the device holds of each slot's steps (a family that cannot
        # rewind; `_loop` sends them when they change): nothing.
        self._steps = None

    def _free_slot_pages(self, slot: int):
        st = self._slots[slot]
        if st.seq_id:
            self._alloc.free(st.seq_id)
            self._tables[slot, :] = self._dummy_page
            # The kernel's work follows `_lens`: an empty slot costs the
            # one dummy page its garbage write lands in, not the pages
            # of the stream that left.
            self._lens[slot] = 0
            # (The device's cursor of this slot ran on to the chunk's end.)
            self._dirty.update(_SLOT)
            st.seq_id = ""

    def _count_paged_pages(self):
        """Table pages the paged kernel visits in the chunk about to be
        dispatched, against those the table holds: step k of the chunk
        attends over `_lens + k + 1` tokens of every slot. Returns this
        chunk's own two counts (they ride on its `engine.decode.wait`)."""
        steps = 1 + np.arange(self.decode_chunk)
        tokens = self._lens[:, None].astype(np.int64) + steps
        live = int((-(-tokens // self.page_size)).sum())
        table = tokens.size * self._np_pages
        self.paged_pages_live += live
        self.paged_pages_table += table
        return live, table

    def _steps_to_take(self) -> np.ndarray:
        """Steps of the next chunk each slot may advance: as many as its
        consumer's queue has room for, none for a slot that is empty. (A
        full queue parks the slot, as a refused offer does where steps can
        be re-run.) A stream owed fewer runs past its end, as one that
        meets its eos does: into pages reserved for that and state its
        slot's next prefill replaces. So a slot's steps change with who
        holds it and with its consumer's pace, not chunk by chunk."""
        steps = np.zeros(self.max_batch, np.int32)
        for i, st in enumerate(self._slots):
            h = st.request
            if h is None:
                continue
            owed = h.sampling.max_new_tokens - st.generated
            steps[i] = min(self.decode_chunk, h.room())
            if steps[i] < min(self.decode_chunk, owed):
                self._parked_events += 1
        return steps

    def _emit(self, slot: int, tok: int) -> bool:
        """Book one token for the stream (`_hand_off` wakes its consumer,
        not this). False = the consumer's bounded queue is full: the
        caller must NOT commit the token — the slot parks (its decode
        cursor stays put) and the same token is re-produced next chunk
        once the consumer drains."""
        st = self._slots[slot]
        handle = st.request
        if not handle._offer(tok):
            self._parked_events += 1
            return False
        st.generated += 1
        st.history.append(tok)
        sp = handle.sampling
        ended = (sp.eos_token is not None and tok == sp.eos_token) or \
            st.generated >= sp.max_new_tokens
        self._booked[handle] = ended
        if ended:
            st.request = None
            # The stream's pages return to the pool the moment it
            # completes — this is what lets a deferred request admit on
            # the next loop pass.
            self._free_slot_pages(slot)
        return True

    def _idle(self, open_span, why: str):
        """The loop has nothing to do for a while (`engine.idle`, outside
        any pass): what is booked is handed over first; then the open
        span of that kind, begun if there is none."""
        self._hand_off()
        if open_span is not None and open_span.attrs["why"] == why:
            return open_span
        if open_span is not None:
            open_span.end()
        return tracing.span("engine.idle", why=why).begin()

    def _hand_off(self) -> None:
        """Every stream's booked tokens to its consumer, a stream that
        ended its end with them: one turn of its lock a stream. The loop
        calls this when it has queued the chip's next program and is about
        to wait for the chip (after a chunk's dispatch, before a prefill's
        fetch), or when it has nothing to queue (idle, quiesce, a failure,
        the loop's end): a woken consumer competes for the interpreter
        lock, so none is woken between a fetch and the next dispatch, and
        nothing booked is held over a wait."""
        if not self._booked:
            return
        span = tracing.span("engine.handoff").begin()
        tokens = sum(handle._hand_over(end=ended)
                     for handle, ended in self._booked.items())
        span.end(streams=len(self._booked), tokens=tokens,
                 ended=sum(self._booked.values()))
        self._booked.clear()

    def _on_chip(self, kind: str, out, queued_ns: int, **attrs) -> _Program:
        """(The loop thread, when a program's last dispatch call has
        returned.) The program takes its number, and the watcher is handed
        it. `queued_ns`: the loop's clock when the program's FIRST dispatch
        call returned, which is when a chip with nothing queued has begun
        it (a prefill group's other two dispatches follow the first by
        1-3 ms of host time, which a stamp at the last would take off a
        starved group's span)."""
        program = _Program(next(self._programs), kind, out, queued_ns, attrs)
        self._watched.put(program)
        return program

    def _watch(self) -> None:
        """(Its own thread.) The chip's ledger: blocks on every program the
        loop dispatched, in the chip's order, and writes its ONE
        `chip.program` span (PERF.md section 3), whose interval is the
        chip's and not any host thread's. It starts when the program was
        queued or when the program before it ended, whichever is later
        (`starved_ns`: how long the chip had nothing queued between the
        two). It ends at the EARLIER of two stamps of one event: this
        thread's, taken when its wait returns, and the loop's, left by its
        own fetch of the same output (`fetched_ns`) where that is already
        there; both wake on the program's end, and the one that loses the
        interpreter lock reads late (`seen_by`: whose stamp the end is;
        `late_ns`: how much later this thread read where the loop's was
        first). Then it tells the loop, which may be waiting for an arrival
        behind the program (`_admit_behind`), that it has left the chip."""
        before = time.monotonic_ns()    # the end of the program before
        while (program := self._watched.get()) is not None:
            try:
                program.out.block_until_ready()
            except Exception:  # noqa: S110 (the loop's own fetch raises it)
                pass
            seen = time.monotonic_ns()
            fetched = program.fetched_ns
            by_fetch = 0 < fetched <= seen
            ended = fetched if by_fetch else seen
            with self._arrivals:
                self._chip_done = program.seq
                self._arrivals.notify()
            queued = program.queued_ns
            tracing.record_span(
                "chip.program", max(queued, before), t1_ns=ended,
                kind=program.kind, seq=program.seq, queued_ns=queued,
                starved_ns=max(0, queued - before),
                seen_by="fetch" if by_fetch else "watch",
                late_ns=seen - ended, **program.attrs)
            before = ended

    def _next_fits(self) -> bool:
        """(Under `_arrivals`.) The next request in line is one to prefill
        here (no `_Prefilled` pack), and its prefill's outputs (logits and
        fresh state: allocated at its dispatch, released when its write
        and sampling have run) fit beside those of the prefills in flight:
        together no more than the most that ONE prefill of this engine
        has held, which is what an admission at the top of a pass holds.
        Sizes are the programs' own, as dispatched; a shape not yet seen
        has none and goes alone."""
        if not self._pending or isinstance(self._pending[0][0], _Prefilled):
            return False
        if not self._in_flight:
            return True
        bucket = max(self._bucket(len(self._pending[0][0])), self.page_size)
        held = self._prefill_bytes.get((1, bucket))
        return held is not None and held + sum(
            f.held for f in self._in_flight) \
            <= max(self._prefill_bytes.values())

    def _empty_slots(self) -> list:
        """Slots that hold no stream and are not spoken for by a prefill
        in flight."""
        return [i for i, st in enumerate(self._slots)
                if st.request is None and not st.seq_id]

    def _gather(self, free: list, under_chunk: bool = False) -> list:
        """Waiting requests, first come first, into the slots of `free`
        (shortened by those taken), pages reserved for each: (slot,
        seq_id, prompt, handle). Admission gates on pool pages: a dry pool
        defers the request (FIFO) until completions free pages, and
        nothing behind it is taken. `under_chunk`: one request at most,
        and only one that `_next_fits`."""
        cands: list = []
        while free and not (under_chunk and cands):
            from_deferred = bool(self._deferred)
            if from_deferred:
                prompt, handle = self._deferred[0]
            else:
                with self._arrivals:
                    if not (self._next_fits() if under_chunk
                            else self._pending):
                        break
                    prompt, handle = self._pending.popleft()
            slot = free[0]
            try:
                seq_id = self._reserve_paged(slot, prompt, handle)
            except MemoryError:
                # Pool dry: keep FIFO order and stop admitting until
                # a completion frees pages.
                if not from_deferred:
                    self._deferred.append((prompt, handle))
                break
            except Exception as e:  # surfacing beats a dead stream
                if from_deferred:
                    self._deferred.pop(0)
                handle._finish(e)
                continue
            if from_deferred:
                self._deferred.pop(0)
            free.pop(0)
            cands.append((slot, seq_id, prompt, handle))
            # Slot and pages are this request's: its wait is over.
            tracing.record_span("request.queue", handle._submit_ns,
                                rid=handle.rid,
                                prompt_len=handle.prompt_len,
                                deferred=from_deferred)
        return cands

    def _admit(self, free: list, under_chunk: bool) -> None:
        """One `engine.admit`: the requests `_gather` takes, prefilled
        together (see _admit_paged_group: sequential slot prefills were
        the measured end-to-end serving bottleneck at large batch)."""
        admit = tracing.span("engine.admit", under_chunk=under_chunk).begin()
        cands = self._gather(free, under_chunk)
        if cands:
            self._admit_paged_group(cands, under_chunk)
        admit.end(admitted=len(cands), deferred=len(self._deferred),
                  rids=[c[3].rid for c in cands])

    def _admit_behind(self, program: _Program, free: list) -> None:
        """`program` (a decode chunk, or a prefill queued behind one) is
        on the chip and the slots of `free` are
        empty (at the chunk's dispatch; after its walk, those it freed as
        well) and not spoken for: until the program is done, a plain
        request that arrives (or had arrived) is given one of them and
        its prefill queued behind what is on the chip, one request a
        prefill, so the chip goes from one program to the next while the
        host is still fetching and walking. Nothing here needs the
        chunk's tokens; the first tokens are fetched after its walk. One
        wait serves both ends: an arrival and the watcher's word that the
        program is done wake the same condition, so the fetch that follows
        finds its tokens there and nothing polls. Where no slot is empty
        (or a request waits for pages: nothing passes it) the loop goes
        straight to that fetch, as it always did."""
        if not free or self._deferred:
            return
        while True:
            with tracing.span("engine.chip.wait"), self._arrivals:
                self._arrivals.wait_for(
                    lambda: self._chip_done >= program.seq or (
                        free and not self._deferred and self._next_fits()))
                if self._chip_done >= program.seq:
                    return
            self._admit(free, under_chunk=True)

    def _loop(self):
        jnp = self._jnp
        # One rule orders a pass: between the fetch of chunk N and the
        # dispatch of chunk N+1 this thread does only what that dispatch
        # needs (the walk's bookkeeping, admissions into slots the walk
        # freed, the commit of first tokens, the build); everything else
        # it does while a program is queued on the chip. So a chunk's
        # tokens are booked in the walk and handed to their streams after
        # the next dispatch (`_hand_off`), and an arrival that finds a slot
        # empty is admitted and prefilled behind the chunk on the chip, or
        # behind a prefill already queued there (`_admit_behind`). The
        # chip's order of programs is what it was: chunk N, prefills with
        # their writes and sampling, chunk N+1.
        #
        # What the host does here leaves spans (`util/tracing.py`; PERF.md
        # section 3 has the names): `engine.pass` for an iteration that
        # admits or dispatches, its phases inside it, `engine.idle` between.
        idle = None
        while not self._stop.is_set():
            # Drain quiesce: ack and idle at a tick boundary — every
            # admitted token is committed and handed over, so slot/KV
            # state is a consistent snapshot for the evacuation path.
            if self._quiesce.is_set():
                idle = self._idle(idle, "quiesce")
                self._quiet.set()
                self._stop.wait(0.01)
                continue
            decoding = [s for s in self._slots if s.request is not None]
            admitting = len(decoding) < self.max_batch and (
                bool(self._deferred) or bool(self._pending))
            if not admitting:
                if not decoding:
                    # Nothing in flight: block for a request.
                    idle = self._idle(idle, "no_request")
                    with self._arrivals:
                        if not self._arrivals.wait_for(
                                lambda: self._pending, timeout=0.05):
                            continue
                    admitting = True
                elif all(s.request.backlog_full() for s in decoding):
                    # Backpressure: if EVERY decoding stream's consumer
                    # queue is full, a decode chunk would produce only
                    # parked tokens — skip the dispatch and give the
                    # consumers time to drain.
                    idle = self._idle(idle, "backpressure")
                    self._stop.wait(0.002)
                    continue
            if idle is not None:
                idle.end()
                idle = None
            loop_pass = tracing.span("engine.pass").begin()
            # Admit as many waiting requests as there are free slots,
            # without stalling slots that are mid-decode: here, together,
            # the ones that waited for a slot the last walk freed (or for
            # pages, behind a pack, for room beside the prefills in flight,
            # or with nothing on the chip); the others were admitted
            # behind the last chunk.
            if admitting:
                self._admit(self._empty_slots(), under_chunk=False)
            decoding = [s for s in self._slots if s.request is not None]
            if not decoding:
                self._hand_off()
                loop_pass.end()
                continue
            if all(s.request.backlog_full() for s in decoding):
                # (Backpressure again: what was admitted filled its queue.)
                loop_pass.end()
                idle = self._idle(idle, "backpressure")
                self._stop.wait(0.002)
                continue
            # One decode CHUNK for every slot (inactive slots compute
            # garbage on their stale state — discarded host-side; slots
            # finishing mid-chunk have their overshoot discarded too).
            # State that cannot be rewound: every slot's steps are sized
            # now, by what its consumer's queue takes, and the program
            # holds it still past them.
            build = tracing.span("engine.decode.build").begin()
            steps = None if self.family.rewinds else self._steps_to_take()
            slot_steps = len(decoding) * self.decode_chunk \
                if steps is None else int(steps.sum())
            self.state_slot_steps += slot_steps
            if steps is not None and not np.array_equal(steps, self._steps):
                self._steps = steps
                self._dirty.add("steps")
            try:
                pages_live, pages_table = self._count_paged_pages()
                dev = self._dev
                uploaded = len(self._dirty)
                self.decode_passes += 1
                self.decode_passes_clean += not uploaded
                if uploaded:
                    # (`jnp.array`: a copy, where the CPU backend would
                    # otherwise keep reading the mirror the walk writes.)
                    with tracing.span("engine.decode.upload"):
                        for name in self._dirty:
                            dev[name] = jnp.array(
                                getattr(self, _MIRRORS[name]))
                        self._dirty.clear()
                args = (self.params, dev["token"], dev["pos"], self._pools,
                        dev["tables"], dev["lens"], dev["temps"],
                        dev["top_ks"], dev["top_ps"], self._rng_base,
                        dev["chunk_no"],
                        *(() if steps is None else (dev["steps"],)))
                build.end(uploaded=uploaded)
                with tracing.span("engine.decode.dispatch"):
                    (toks, self._pools, dev["token"], dev["pos"],
                     dev["lens"], dev["chunk_no"]) = \
                        self._decode_chunk_paged(*args)
                    program = self._on_chip("decode", toks,
                                            time.monotonic_ns(),
                                            active=len(decoding),
                                            steps=slot_steps)
                    # The inputs the carry has replaced die here, with the
                    # device at work: an array's destructor gives the
                    # interpreter lock away, and before the dispatch that
                    # would put the loop behind every consumer awake.
                    del args
                    self._chunk_no += 1
                # The chip has its next program: now the streams get what
                # the last walk and the commits booked.
                self._hand_off()
                free = self._empty_slots()
                self._admit_behind(program, free)
                # The chunk's ONE `engine.decode.wait` (its counts ride on
                # it): the whole wait where no slot was empty, else what
                # is left of it after `engine.chip.wait`.
                wait = tracing.span("engine.decode.wait",
                                    active=len(decoding), steps=slot_steps,
                                    pages_live=pages_live,
                                    pages_table=pages_table).begin()
                toks = np.asarray(toks)  # (K, B), the step's counts after
                program.fetched_ns = time.monotonic_ns()
                B = self.max_batch
                wait.end(**self._count(self._step_counters, toks[:, B:]))
                toks = toks[:, :B]
            except Exception as e:
                # A decode failure (device OOM, donated-buffer misuse, ...)
                # must not strand waiters on a dead thread: fail loudly and
                # keep serving subsequent requests on fresh state.
                self._fail_all(e)
                self._init_paged_state()
                loop_pass.end(error=type(e).__name__)
                continue
            walk = tracing.span("engine.walk").begin()
            emitted = finished = 0
            parked = self._parked_events
            for i, st in enumerate(self._slots):
                if st.request is None:
                    continue
                take = toks.shape[0] if steps is None else int(steps[i])
                for kstep in range(take):
                    tok = int(toks[kstep, i])
                    if not self._emit(i, tok):
                        # Consumer backlog full: park WITHOUT committing.
                        # Decode re-runs from the committed cursor next
                        # chunk — safe because decode writes KV at index
                        # lens before attending and masks kpos<=qpos, so
                        # the uncommitted steps' writes are garbage that
                        # is simply rewritten. The device's cursor ran on:
                        # the host's, now behind it, is sent again.
                        self._dirty.update(_CURSOR)
                        break
                    emitted += 1
                    if st.request is None:  # eos/max_new hit mid-chunk:
                        finished += 1       # _emit freed the slot
                        break
                    self._lens[i] += 1
                    self._pos[i] += 1
                    self._token[i] = tok
            walk.end(emitted=emitted, finished=finished,
                     parked=self._parked_events - parked)
            if self._in_flight:
                # First tokens of what was admitted behind the chunk: the
                # chip has been at those prefills since the chunk ended,
                # and arrivals are served behind them as behind the chunk.
                commit = tracing.span("engine.commit").begin()
                rows = 0
                # (the walk's slots too: what arrives from here on was not
                # waiting when they were freed)
                free = self._empty_slots()
                while self._in_flight:
                    flight = self._in_flight[0]
                    rows += len(flight.requests)
                    self._finish_prefill(flight, free)
                    self._in_flight.pop(0)
                commit.end(rows=rows)
            loop_pass.end()
        self._hand_off()
        if idle is not None:
            idle.end()


# ---------------------------------------------------------------------------
# Serve integration
# ---------------------------------------------------------------------------


class LLMServer:
    """Deployment callable hosting one LLMEngine per replica.

    Use with @serve.deployment:

        @serve.deployment
        class Chat(LLMServer):
            def __init__(self):
                cfg, params = load_my_model()
                super().__init__(cfg, params, max_batch=8, max_len=2048)

        serve.run(Chat.bind())
        handle.options(stream=True).remote({"prompt_tokens": [...],
                                            "max_new_tokens": 32})
    """

    def __init__(self, cfg, params, *, max_batch: int = 4,
                 max_len: int = 1024, decode_chunk: int = 8,
                 page_size: int = 64, kv_pool_tokens: int = 0,
                 stream_buffer: int = 256):
        self.engine = LLMEngine(cfg, params, max_batch=max_batch,
                                max_len=max_len, decode_chunk=decode_chunk,
                                page_size=page_size,
                                kv_pool_tokens=kv_pool_tokens,
                                stream_buffer=stream_buffer)

    def report_metrics(self) -> dict:
        return self.engine.report_metrics()

    def __call__(self, payload: dict):
        sp = SamplingParams(
            max_new_tokens=int(payload.get("max_new_tokens", 64)),
            temperature=float(payload.get("temperature", 0.0)),
            top_k=int(payload.get("top_k", 0)),
            top_p=float(payload.get("top_p", 1.0)),
            eos_token=payload.get("eos_token"))
        handle = self.engine.submit(payload["prompt_tokens"], sp)
        for tok in handle:
            yield tok
