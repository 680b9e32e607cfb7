"""Continuous-batching serving engine over the paged KV cache.

One ``ServingEngine`` owns the device state (params + cache buffers + the
two jitted step programs of its model family: ``serving/registry.py``
finds the family, ``serving/decode.py`` is GPT's, ``serving/swa_moe.py`` the
windowed-attention sparse-expert one's, ``serving/gdn_mla.py`` the
linear-attention / latent-attention one's) and the host state (slot table,
block tables, page allocator, request queues). Whatever the family, the
pages the allocator hands out and the block table a request holds are those
of the layers that keep every token (keys and values, or latents); a
family's other caches (a ring a slot for window layers, a recurrent state a
slot for linear-attention layers) cost the host nothing. The scheduler runs
the vLLM-style loop, one ``step()`` per iteration, and keeps the device
fed: the device work of tick N+1 is dispatched BEFORE the tokens of tick N
are fetched (order of a tick: admit → dispatch a chunk → schedule → dispatch
decode N+1 → fetch and emit decode N → fetch and emit a last chunk's first
token → gauges; docs/serving.md "The tick"). Exactly one decode step is in
flight: what the host learns late — an eos, and that a cancel, shed or
preemption came too late for the step already running — it learns one step
late, never more. The last tokens stay on the device; the host keeps its
books (lengths, pages, counts) at dispatch and stamps every latency at
emit, once the value is on the host:

1. **admit** — waiting requests take a free decode slot + a **lazy** page
   grant: the prompt's pages plus ``alloc_watermark`` headroom pages
   (vLLM-style; ``lazy_alloc: false`` restores the old reserve-up-front
   ``ceil((prompt + max_new) / page_size)`` for A/B measurement).
   Requests the pool could NEVER hold are refused at ``submit`` (OOM
   admission refusal), requests that merely don't fit *right now* wait;
2. **prefill** — ONE chunk (``prefill_chunk`` tokens) of the oldest
   prefilling request is forwarded; long prompts therefore spread over
   several steps instead of stalling the decode batch, and the final
   chunk's logits yield the request's first token (TTFT), which joins
   the decode batch on the device in the same tick;
3. **decode** — one token for every RUNNING slot in a single static-shape
   step; each running request's block table grows one page at a time as
   its length crosses page boundaries, and when the pool runs dry the
   YOUNGEST live request is **preempted**: pages freed, state reset,
   re-enqueued at the head of the admission queue (decode is idempotent —
   the re-run regenerates the same greedy tokens, the loss-free-recovery
   property the router's re-dispatch already relies on). New requests
   join at the next step boundary, finished ones (eos /
   ``max_new_tokens``) free their pages and leave when their last token
   is emitted — no retrace in any direction. A finish by length is known
   from a count and the row is simply not dispatched again; an eos is a
   value, so the one row-step computed past it is dropped
   (``serving_overrun_rows``).

Telemetry rides the PR 1 metrics registry (``serving_ttft`` /
``serving_inter_token`` histograms; queue-depth / active-request /
page-occupancy gauges), serving events land in the PR 8 flight ring, and
``serving_snapshot()`` emits the record shape
``observability/schema.py:SERVING_RECORD_SCHEMA`` validates. Every tick
and each of its phases is a profiler annotation
(``observability/trace.py:HOT_LOOP_SPANS``, ``serve.*``) and a
``time.monotonic`` reading kept for the last tick (``last_tick``); a first
token's wait is recorded in three parts (``serving_queue_wait`` +
``serving_prefill_wait`` + ``serving_prefill_run`` = ``serving_ttft``).
The engine knows the period between two drains (``serving_tick``), never a
program's device time: that is the trace's to say.
"""

from __future__ import annotations

import dataclasses
import threading
import time
from collections import OrderedDict, deque
from typing import Any, Callable, Optional

import jax
import numpy as np

# the serving engine is a sharded path (pool over fsdp/tensor), and the
# mesh substrate pins jax_threefry_partitionable at import — BEFORE any
# seeded param init, so a replica's init matches the trainer's and every
# sibling replica's regardless of which modules loaded first
# (parallel/mesh.py documents the layout-variance this prevents)
import fleetx_tpu.parallel.mesh  # noqa: F401  (imported for its config pin)
from fleetx_tpu.observability import flight, tsan
from fleetx_tpu.observability.flight import EventRing
from fleetx_tpu.observability.metrics import get_registry
from fleetx_tpu.observability.slo import SLORegistry
from fleetx_tpu.observability.trace import span
from fleetx_tpu.ops import paged_attention as PA
from fleetx_tpu.serving import registry
from fleetx_tpu.serving.paged_cache import NULL_PAGE, PageAllocator
from fleetx_tpu.serving.programs import SamplingParams
from fleetx_tpu.utils.env import log_compile
from fleetx_tpu.utils.log import logger

#: request lifecycle states
WAITING, PREFILL, RUNNING, FINISHED, REFUSED = (
    "waiting", "prefill", "running", "finished", "refused")


@dataclasses.dataclass
class ServingConfig:
    """The ``Serving:`` YAML section (docs/serving.md "Sizing the pool")."""

    max_batch: int = 8          # decode slots (static batch dim)
    page_size: int = 16         # tokens per KV page
    num_pages: int = 64         # pool pages INCLUDING the reserved null page
    max_seq_len: int = 0        # 0 → model max_position_embeddings
    prefill_chunk: int = 32     # prompt tokens forwarded per step
    quantize_decode: bool = False  # int8-act decode (Quantization bits)
    # decode attention path: when True AND ``ops/paged_attention.py``'s
    # support predicates admit this (head geometry, VMEM tile budget,
    # pool divisibility on a sharded mesh), decode runs the in-kernel
    # Pallas paged attention — no ``[B, pages*page_size]`` gather
    # materialization. Falls back to the gather path otherwise. The
    # choice is made ONCE at engine construction so the jit cache stays
    # pinned at one decode program (the no-retrace contract).
    paged_kernel: bool = True
    # page lifecycle: True (default) admits on prompt pages +
    # ``alloc_watermark`` headroom and grows page-by-page during decode,
    # preempting the youngest request when the pool runs dry; False
    # restores reserve-up-front (``prompt + max_new`` pages at admission)
    # for A/B measurement
    lazy_alloc: bool = True
    alloc_watermark: int = 1    # headroom pages granted at lazy admission
    # checkpoint directory to restore params from (tools/serve.py feeds it
    # through the PR 7 integrity-verified loader, restoring each leaf
    # DIRECTLY onto its registry sharding when the replica runs a mesh);
    # None = seeded init
    ckpt_dir: Optional[str] = None
    # LoRA adapter artifact directory (finetune/checkpoint.py): verified
    # against the base weights + registry fingerprint, then merged — the
    # decode programs run the fine-tuned weights at zero adapter cost
    # (docs/finetune.md); requires ckpt_dir
    adapter_dir: Optional[str] = None
    # per-request lifecycle tracing (docs/serving.md "Observability"):
    # how many finished/refused timelines stay retrievable behind the
    # ``trace`` verb, and the per-timeline event-ring capacity
    trace_requests: int = 256
    trace_events: int = 128
    # declarative SLO targets (observability/slo.py) — the ``Serving.slo``
    # YAML block; None disables SLO evaluation entirely
    slo: Optional[dict] = None
    # admission-queue bound (docs/serving.md "Fault tolerance"): submissions
    # past this many waiting requests are refused ``overloaded`` with a
    # ``retry_after_s`` hint instead of queueing unboundedly; 0 = unbounded
    max_queue: int = 256
    # router behaviour block (``Serving.router``) — consumed by
    # ``serving/router.py``, validated eagerly in ``process_serving_config``
    # and forwarded by ``tools/serve.py --router``; the engine itself
    # never reads it
    router: Optional[dict] = None

    @classmethod
    def from_dict(cls, d: Optional[dict]) -> "ServingConfig":
        """Build from a YAML ``Serving`` section (unknown keys rejected)."""
        known = {f.name for f in dataclasses.fields(cls)}
        d = dict(d or {})
        unknown = set(d) - known
        assert not unknown, f"unknown Serving config keys: {sorted(unknown)}"
        return cls(**{k: v for k, v in d.items() if v is not None})


@dataclasses.dataclass
class ServingRequest:
    """One in-flight generation request and its bookkeeping."""

    id: str
    prompt: list
    max_new_tokens: int
    callback: Optional[Callable] = None
    state: str = WAITING
    slot: int = -1
    pages: list = dataclasses.field(default_factory=list)
    prefill_pos: int = 0
    tokens: list = dataclasses.field(default_factory=list)
    # tokens whose computation has been DISPATCHED (the first by the last
    # prefill chunk, one a decode step): ahead of ``len(tokens)`` by what is
    # in flight; a row stops being dispatched when this reaches
    # ``max_new_tokens``, one tick before its last token is emitted
    dispatched: int = 0
    error: Optional[str] = None
    submitted_at: float = 0.0
    # the two marks between submission and first token (monotonic, stamped
    # once: a preempted request keeps its first admission and first chunk)
    admitted_at: Optional[float] = None
    prefill_started_at: Optional[float] = None
    first_token_at: Optional[float] = None
    last_token_at: Optional[float] = None
    finished_at: Optional[float] = None
    # admission recency: monotonically minted at every (re-)admission —
    # the preemption policy's youngest-first ordering key
    admit_seq: int = -1
    preemptions: int = 0
    # client deadline (seconds from submission); None = no deadline. An
    # admission-time refusal classifies it (``overloaded``/``unmeetable``)
    # and fills ``retry_after_s``; an in-flight expiry sheds the request
    # at the next decode-tick boundary (``deadline_shed``)
    deadline_s: Optional[float] = None
    retry_after_s: Optional[float] = None

    @property
    def ttft_s(self) -> Optional[float]:
        """Seconds from submission to the first generated token."""
        if self.first_token_at is None:
            return None
        return self.first_token_at - self.submitted_at


#: lifecycle event taxonomy (docs/serving.md "Observability") — the order
#: a healthy request walks them; ``refused`` replaces the admitted→finished
#: span for drain/OOM refusals, ``drain`` marks a replica preemption
#: landing while the request was live, ``page_grow`` stamps each lazy
#: block-table extension, and ``preempted`` marks a pool-pressure swap-out
#: (the request loops back to ``admitted`` afterwards)
TIMELINE_EVENTS = ("queued", "admitted", "prefill_started", "prefill_chunk",
                   "first_token", "decode_tick", "page_grow", "preempted",
                   "finished", "refused", "drain", "deadline_shed")

#: milestone events whose first timestamp is pinned outside the ring so
#: attribution survives decode-tick eviction on long generations
_MILESTONES = ("queued", "admitted", "prefill_started", "first_token",
               "finished", "refused")


class RequestTimeline:
    """One request's bounded lifecycle event ring + derived attribution.

    Events ride an ``observability/flight.py``-style ``EventRing``: a
    long decode drops its oldest ticks (counted, never silent) while the
    milestone timestamps are pinned on the object, so the queue/prefill/
    decode decomposition stays exact however many events fell off.
    """

    def __init__(self, rid: str, capacity: int = 128):
        self.id = str(rid)
        self.ring = EventRing(capacity)
        self.state = "open"  # open | finished | refused
        self._marks: dict = {}
        self._pages = 0
        self._chunks = 0
        self._ticks = 0

    def note(self, name: str, **data: Any) -> None:
        """Append one wall-clock-stamped lifecycle event."""
        evt = {**data, "t": time.time(), "name": name}
        if name in _MILESTONES and name not in self._marks:
            self._marks[name] = evt["t"]
            if name == "admitted":
                self._pages = int(data.get("pages") or 0)
        if name == "prefill_chunk":
            self._chunks += 1
        elif name == "decode_tick":
            self._ticks += 1
        self.ring.append(evt)

    def events(self) -> list:
        """Snapshot of the event ring, oldest first."""
        return self.ring.snapshot()

    def attribution(self) -> dict:
        """Per-phase latency decomposition from the milestone timestamps.

        ``queue_s`` (queued→admitted) + ``prefill_s`` (admitted→first
        token) = ``ttft_s``, then ``decode_s`` (first token→finished):
        TTFT regressions name their phase. ``prefill_s``
        splits at the dispatch of the request's own first chunk into
        ``prefill_wait_s`` (behind other prompts' chunks) and
        ``prefill_run_s`` (its own chunks). Spans whose endpoints haven't
        happened are None, never a fake zero.
        """
        t = self._marks

        def span(a: str, b: str) -> Optional[float]:
            return (t[b] - t[a]) if a in t and b in t else None

        total = span("queued", "finished")
        if total is None:
            total = span("queued", "refused")
        return {
            "queue_s": span("queued", "admitted"),
            "prefill_s": span("admitted", "first_token"),
            "prefill_wait_s": span("admitted", "prefill_started"),
            "prefill_run_s": span("prefill_started", "first_token"),
            "decode_s": span("first_token", "finished"),
            "ttft_s": span("queued", "first_token"),
            "total_s": total,
            "pages": self._pages,
            "prefill_chunks": self._chunks,
            "decode_ticks": self._ticks,
        }

    def to_dict(self) -> dict:
        """The ``trace`` verb's JSON payload for this request."""
        return {
            "id": self.id, "state": self.state, "events": self.events(),
            "events_total": self.ring.total,
            "events_dropped": self.ring.dropped,
            "attribution": self.attribution(),
        }


class TimelineStore:
    """Bounded id → timeline map behind the ``trace`` verb.

    The engine thread writes; connection-handler threads read
    concurrently, so every map mutation holds the lock (the per-timeline
    rings carry their own). Finished timelines stay retrievable until
    ``max_requests`` newer requests evict them, insertion-ordered — the
    flight-ring stance applied per request.
    """

    def __init__(self, max_requests: int = 256,
                 events_per_request: int = 128):
        self.max_requests = max(int(max_requests), 1)
        self.events_per_request = max(int(events_per_request), 8)
        self._lock = tsan.lock("serving.timelines")
        self._timelines: "OrderedDict[str, RequestTimeline]" = OrderedDict()

    def open(self, rid: str) -> RequestTimeline:
        """Get-or-create the timeline for one request id."""
        with self._lock:
            tl = self._timelines.get(str(rid))
            if tl is None:
                tl = RequestTimeline(rid, self.events_per_request)
                self._timelines[str(rid)] = tl
                while len(self._timelines) > self.max_requests:
                    self._timelines.popitem(last=False)
            return tl

    def get(self, rid: str) -> Optional[RequestTimeline]:
        """The timeline for ``rid`` (None when unknown or evicted)."""
        with self._lock:
            return self._timelines.get(str(rid))

    def note(self, rid: str, name: str, **data: Any) -> None:
        """Append one event onto an existing timeline (no-op on unknown
        ids — a timeline evicted mid-flight must not resurrect empty)."""
        tl = self.get(rid)
        if tl is not None:
            tl.note(name, **data)

    def live(self) -> list:
        """Every still-open timeline (the drain/crash dump set)."""
        with self._lock:
            return [tl for tl in self._timelines.values()
                    if tl.state == "open"]


#: a tick this many times the mean of the last ``SLOW_TICK_WINDOW`` working
#: ticks (at least ``SLOW_TICK_MIN`` of them) logs its phases
SLOW_TICK_FACTOR, SLOW_TICK_WINDOW, SLOW_TICK_MIN = 5.0, 32, 8


def _tick_span(name: str, **args: Any) -> span:
    """A span of the tick: annotation and Chrome event, never a flight note
    (nine a tick would push the serving events out of a replica's ring)."""
    return span(name, flight_note=False, **args)


class _Phase:
    """One phase of a tick: the ``serve.<key>`` profiler annotation, and the
    engine's clock read on both boundaries into the tick's phase seconds."""

    __slots__ = ("_engine", "_key", "_span", "_t0")

    def __init__(self, engine: "ServingEngine", key: str, **args: Any):
        self._engine, self._key = engine, key
        self._span = _tick_span("serve." + key, **args)

    def __enter__(self):
        self._span.__enter__()
        self._t0 = self._engine._clock()
        return self

    def __exit__(self, *exc):
        eng = self._engine
        eng._phase_end = now = eng._clock()
        eng.last_tick[self._key] = \
            eng.last_tick.get(self._key, 0.0) + now - self._t0
        return self._span.__exit__(*exc)


@dataclasses.dataclass
class _InFlight:
    """The ONE decode step the device runs while the host goes on: what it
    will have sampled (still on the device, its copy to the host started),
    the family's step counters, and the rows it ran, each by identity —
    ``(request, admit_seq)``. A row whose request was preempted, cancelled,
    shed or finished between this dispatch and its emit is void."""

    toks: Any
    counters: list
    rows: list
    lens: np.ndarray        # the lengths the call was given (a snapshot)


class ServingEngine:
    """Request-level decode runtime (see module docstring for the loop)."""

    def __init__(self, model_cfg: Any, params: Any,
                 serving: Optional[ServingConfig] = None,
                 sampling: Optional[SamplingParams] = None,
                 eos_token_id: int = 50256, mesh: Optional[Any] = None,
                 seed: int = 0):
        from flax.core import meta

        self.cfg = model_cfg
        self.serving = serving or ServingConfig()
        self.sampling = sampling or SamplingParams()
        self.eos_token_id = int(eos_token_id)
        self.mesh = mesh
        sc = self.serving
        self.max_seq_len = int(sc.max_seq_len) or model_cfg.max_position_embeddings
        assert self.max_seq_len <= model_cfg.max_position_embeddings, \
            "Serving.max_seq_len exceeds the model's position table"
        self.pages_per_req = -(-self.max_seq_len // sc.page_size)

        # cast ONCE, here: the programs convert no parameter (decode.py)
        self.family = registry.family_of(model_cfg)
        given = meta.unbox(params)
        self.params = self.family.serving_params(given, model_cfg)
        cast = [a for a, b in zip(jax.tree.leaves(given),
                                  jax.tree.leaves(self.params)) if a is not b]
        weights = "%d leaves cast%s, serving tree %d bytes" % (
            len(cast),
            " %s -> %s" % ("/".join(sorted({a.dtype.name for a in cast})),
                           np.dtype(model_cfg.dtype).name) if cast else "",
            sum(a.nbytes for a in jax.tree.leaves(self.params)))
        self.allocator = PageAllocator(sc.num_pages, sc.page_size)
        # the family's cache buffers and its two programs; kernel-vs-gather
        # is decided there, once, so each jit cache stays at one entry. The
        # buffers are donated to every call and rebound from its result
        self._programs = self.family.programs(
            model_cfg, sc, self.sampling, mesh, self.pages_per_req)
        self.cache: list = list(self._programs.cache)
        self._programs.cache = []       # the engine holds the only handles
        self._fns = self._programs.fns
        self.paged_kernel_active = self._programs.paged_kernel_active
        self.cache_bytes = sum(int(a.nbytes) for a in self.cache)
        # the query positions the last decode call was given (kernel path:
        # the serving_page_walk_share gauge counts a tick's folds from them
        # with the helper the kernel's trip count uses)
        self._decoded_lens: Optional[np.ndarray] = None

        self._compiled: set = set()  # programs whose compile was logged

        # host-side scheduler state
        self._slots: list = [None] * sc.max_batch
        self._block_tables = np.full((sc.max_batch, self.pages_per_req),
                                     NULL_PAGE, np.int32)
        self._lens = np.full((sc.max_batch,), -1, np.int32)
        # the last tokens never leave the device: ``decode`` takes the
        # previous call's sampled tokens as they are and ``prefill``'s
        # newest sampled token beside them (merged inside the program)
        self._tokens = self._programs.tokens
        self._fresh_tok: Any = None
        # what the device is ahead of the host by: one decode step, and
        # (inside a tick only) the first token of a prompt's last chunk
        self._inflight: Optional[_InFlight] = None
        self._first: Optional[tuple] = None     # (request, admit_seq, tok)
        self._waiting: deque = deque()
        self._prefilling: deque = deque()
        # one base key, on the host, and the count of programs dispatched:
        # a sampling program folds the count into the key itself
        self._rng = np.asarray(jax.random.PRNGKey(int(seed)))
        self._draws = 0
        self.draining = False
        self.steps = 0
        self._started_at = time.monotonic()
        self.metrics = get_registry()
        # how the decode kernel fetches a fold of each kind of cache: fixed
        # when the programs were built, so the gauges are set once, here
        # (0: no such cache, or the gathered view)
        folds = self._programs.kv_folds
        for kind in ("full", "window", "latent"):
            pages, copies = folds.get(kind, (0, 0))
            self.metrics.gauge(f"serving_kv_fold_pages_{kind}").set(pages)
            self.metrics.gauge(f"serving_kv_fold_copies_{kind}").set(copies)
        # bytes of the caches that are not lists of keys and values (a pool
        # of latents, a constant-size state a slot): 0 in a family without
        self._other_cache_bytes = self.family.cache_bytes(self.cache)
        self._record_stats = self.family.stats_recorder(model_cfg)
        for kind, nbytes in self._other_cache_bytes.items():
            self.metrics.gauge(f"serving_{kind}_cache_bytes").set(nbytes)
        # the tick's own clock (a test may replace it) and what it last
        # read: seconds by phase of the LAST tick only, ``tick`` the whole
        self._clock = time.monotonic
        self.last_tick: dict = {}
        self._phase_end = 0.0
        self._drained_at: Optional[float] = None
        # where the last working tick's period ended (None: the engine was
        # idle since), and whether a dispatched chunk's device time is not
        # inside a recorded period yet
        self._mark: Optional[float] = None
        self._chunk_unpriced = False
        # monotonic id mint: never reset (reset_stats() zeroing the
        # request counter used to recycle ids across bench windows,
        # silently merging two requests' timelines and router bookkeeping)
        self._rid_counter = 0
        # admission recency mint for the preempt-youngest policy; never
        # reset, so ordering survives bench-window stat resets too
        self._admit_seq = 0
        # engine-local gauge freshness: the registry is process-global, so
        # a prior engine's gauge values must not read as THIS engine's
        self._gauges_current = False
        self.timelines = TimelineStore(sc.trace_requests, sc.trace_events)
        self.slo = SLORegistry.from_config(sc.slo, registry=self.metrics)
        # chips this replica occupies: its mesh size, or one device for an
        # unsharded replica — the denominator of requests-per-chip
        self.n_chips = int(mesh.size) if mesh is not None else 1
        # scheduler state is engine-thread-confined by design: handler
        # threads must go through the server's submission queue, never
        # call submit()/step() directly. FLEETX_TSAN=1 enforces that.
        tsan.register_object(self, "serving-engine")
        caches = self.family.describe(model_cfg, sc, self.cache)
        logger.info(
            "serving engine: max_batch=%d pages=%d x %d tokens "
            "(capacity %d token slots/layer), prefill_chunk=%d, "
            "quantize_decode=%s, decode=%s%s, alloc=%s, cache %d bytes%s, "
            "weights: %s",
            sc.max_batch, self.allocator.usable_pages,
            sc.page_size, self.allocator.usable_pages * sc.page_size,
            sc.prefill_chunk, bool(sc.quantize_decode),
            # the kernel's P · V follows the pool's dtype and nothing else
            ("paged_kernel (P·V %s)" % (
                "float32, exact" if self.pool_k.dtype == np.float32
                else "%s, one pass" % self.pool_k.dtype.name))
            if self.paged_kernel_active else "gather",
            "".join(", %s cache %d pages a fold in %d cop%s a buffer"
                    % (kind, pages, copies, "y" if copies == 1 else "ies")
                    for kind, (pages, copies) in sorted(folds.items())),
            "lazy" if sc.lazy_alloc else "reserve", self.cache_bytes,
            caches, weights)

    # the first two cache buffers are the paged pool's K and V in every
    # family (GPT has no others)
    @property
    def pool_k(self):
        return self.cache[0]

    @property
    def pool_v(self):
        return self.cache[1]

    # ------------------------------------------------------------ submission
    def submit(self, prompt: list, max_new_tokens: int,
               request_id: Optional[str] = None,
               callback: Optional[Callable] = None,
               deadline_s: Optional[float] = None) -> ServingRequest:
        """Queue one request; refusals (drain / permanent OOM / deadline)
        come back with ``state == REFUSED`` and ``error`` set, never
        queued. ``deadline_s`` makes admission deadline-aware: a request
        whose projected completion exceeds its deadline is refused up
        front — ``unmeetable`` (its own service time alone blows the
        deadline; retrying won't help until the deadline grows) or
        ``overloaded`` (the queue ahead of it does; ``retry_after_s``
        names the projected drain)."""
        tsan.note_access(self, "submit")
        rid = request_id if request_id is not None \
            else f"req{self._rid_counter}"
        self._rid_counter += 1
        req = ServingRequest(id=str(rid), prompt=[int(t) for t in prompt],
                             max_new_tokens=int(max_new_tokens),
                             callback=callback, submitted_at=time.monotonic(),
                             deadline_s=(float(deadline_s)
                                         if deadline_s is not None else None))
        self.metrics.counter("serving_requests_total").inc()
        self.timelines.open(req.id).note(
            "queued", prompt_len=len(req.prompt),
            max_new=req.max_new_tokens)
        need_tokens = len(req.prompt) + req.max_new_tokens
        need_pages = self.allocator.pages_needed(need_tokens)
        if self.draining:
            return self._refuse(req, "draining")
        if not req.prompt or need_tokens > self.max_seq_len or \
                not self.allocator.fits_ever(need_pages):
            return self._refuse(
                req, f"oom: request needs {need_pages} pages / "
                     f"{need_tokens} tokens; pool holds "
                     f"{self.allocator.usable_pages} pages of "
                     f"{self.allocator.page_size}")
        max_queue = int(self.serving.max_queue or 0)
        if max_queue and len(self._waiting) >= max_queue:
            service, eta = self.projected_completion_s(
                len(req.prompt), req.max_new_tokens)
            req.retry_after_s = round(max(
                (eta or 0.0) - (service or 0.0), 0.05), 3)
            self.metrics.counter("serving_refusals_overloaded").inc()
            return self._refuse(
                req, f"overloaded: admission queue full "
                     f"({len(self._waiting)} >= {max_queue})")
        if req.deadline_s is not None:
            service, eta = self.projected_completion_s(
                len(req.prompt), req.max_new_tokens)
            if service is not None and service > req.deadline_s:
                req.retry_after_s = round(service, 3)
                self.metrics.counter("serving_refusals_unmeetable").inc()
                return self._refuse(
                    req, f"unmeetable: projected service {service:.3f}s "
                         f"exceeds deadline {req.deadline_s:.3f}s")
            if eta is not None and eta > req.deadline_s:
                req.retry_after_s = round(eta - service, 3)
                self.metrics.counter("serving_refusals_overloaded").inc()
                return self._refuse(
                    req, f"overloaded: projected completion {eta:.3f}s "
                         f"(queue {len(self._waiting)}) exceeds deadline "
                         f"{req.deadline_s:.3f}s")
        self._waiting.append(req)
        flight.note("serving", "submit", id=req.id,
                    prompt_len=len(req.prompt))
        return req

    def _measured_mean(self, name: str) -> Optional[float]:
        """Mean of a registry histogram, None before any observation."""
        h = self.metrics.histogram(name)
        count = int(getattr(h, "total_count", 0) or 0)
        if count <= 0:
            return None
        return float(h.total_sum) / count

    def projected_completion_s(self, prompt_len: int, max_new: int):
        """``(service_s, eta_s)`` estimate for a fresh submission.

        ``service_s`` is the request's own cost — prefill chunks at the
        measured mean ``serving_chunk_tick`` (a chunk costs its request one
        tick, whatever else rides in that tick: one chunk is forwarded a
        tick; and the period between two drains in which the device ran a
        chunk is longer than one in which it only decoded, so the mean is
        taken over the chunk-carrying periods alone)
        plus ``max_new`` tokens at the measured mean inter-token latency.
        The engine knows the wall time between drains, not device time per
        program;
        the old per-program timers stopped at a ``device_get`` that a
        chunk other than a prompt's last never reaches, and priced a chunk
        three to six times too low (PERF.md, PR 23). ``eta_s`` adds the queue
        ahead of it: every waiting/prefilling request's own service
        estimate, divided by the decode batch width (decode is batched,
        so queued work drains ``max_batch``-wide, not serially). Both are
        None until the engine has measured at least one chunk-carrying tick
        and one decode tick — admission never refuses on guesswork."""
        pf = self._measured_mean("serving_chunk_tick")
        itl = self._measured_mean("serving_inter_token")
        if pf is None or itl is None:
            return None, None
        chunk = max(int(self.serving.prefill_chunk), 1)

        def est(plen: int, new: int) -> float:
            return -(-plen // chunk) * pf + new * itl

        service = est(max(int(prompt_len), 1), max(int(max_new), 1))
        ahead = sum(est(max(len(r.prompt), 1), max(r.max_new_tokens, 1))
                    for r in list(self._waiting) + list(self._prefilling))
        eta = service + ahead / max(int(self.serving.max_batch), 1)
        return service, eta

    def _refuse(self, req: ServingRequest, why: str) -> ServingRequest:
        req.state, req.error = REFUSED, why
        req.finished_at = time.monotonic()
        self.metrics.counter("serving_requests_refused").inc()
        tl = self.timelines.get(req.id)
        if tl is not None:
            tl.note("refused", why=why)
            tl.state = "refused"
        flight.note("serving", "refuse", id=req.id, why=why)
        if req.callback:
            req.callback(req)
        return req

    # -------------------------------------------------------------- schedule
    def _admit(self) -> None:
        """Waiting → prefill while a slot AND a page grant fit (strict
        FIFO: head-of-line blocking keeps admission fair).

        The grant is the admission policy: lazy (default) asks for the
        prompt's pages plus ``alloc_watermark`` headroom — decode grows
        the rest page-by-page in ``_grow_or_preempt`` — while
        ``lazy_alloc: false`` reserves the worst case up front. Both are
        capped at the worst case, so a zero-decode request never
        over-reserves."""
        sc = self.serving
        while self._waiting:
            req = self._waiting[0]
            try:
                slot = self._slots.index(None)
            except ValueError:
                return
            worst = self.allocator.pages_needed(
                len(req.prompt) + req.max_new_tokens)
            if sc.lazy_alloc:
                need = min(self.allocator.pages_needed(len(req.prompt))
                           + max(int(sc.alloc_watermark), 0), worst)
            else:
                need = worst
            pages = self.allocator.alloc(need)
            if pages is None:
                return
            self._waiting.popleft()
            req.state, req.slot, req.pages = PREFILL, slot, pages
            req.admit_seq = self._admit_seq
            self._admit_seq += 1
            if req.admitted_at is None:
                req.admitted_at = time.monotonic()
            self._slots[slot] = req
            self._block_tables[slot] = NULL_PAGE
            self._block_tables[slot, :need] = pages
            self._lens[slot] = -1  # joins the decode batch after prefill
            self._prefilling.append(req)
            self.timelines.note(req.id, "admitted", slot=slot, pages=need,
                                occupancy=self.allocator.occupancy())
            flight.note("serving", "admit", id=req.id, slot=slot,
                        pages=need)

    def _call(self, name: str, *args):
        """Run one of the two device programs; its first call says what it
        compiles — seconds and Mosaic kernels by name."""
        fn = self._fns[name]
        if name not in self._compiled:
            self._compiled.add(name)
            log_compile(f"serving {name}", fn, *args)
        return fn(*args)

    def _rebind(self, out: tuple) -> tuple:
        """A program's result starts with the cache buffers it was given
        (donated): keep those, return what follows — ``(token(s), logits[,
        counters])``."""
        n = len(self.cache)
        self.cache = list(out[:n])
        return out[n:]

    def _draw(self) -> tuple:
        """``(base key, draw count)`` for the program about to be dispatched:
        two host values, nothing runs on the device for them."""
        draw = np.uint32(self._draws & 0xFFFFFFFF)
        self._draws += 1
        return self._rng, draw

    def _holds(self, req: ServingRequest, admit_seq: int) -> bool:
        """Whether ``req`` still is the admission a dispatch ran it as: not
        preempted, cancelled, shed or finished since."""
        return req.state == RUNNING and req.admit_seq == admit_seq

    def _prefill_step(self) -> bool:
        """Dispatch one chunk of the oldest prefilling request. A prompt's
        last chunk joins the decode batch at once — its sampled token stays
        on the device for this tick's decode call, and is fetched for the
        caller only after that call is dispatched (``_first_token``)."""
        if not self._prefilling:
            return False
        req = self._prefilling[0]
        sc = self.serving
        pos = req.prefill_pos
        index = pos // max(sc.prefill_chunk, 1)
        with _Phase(self, "prefill", rid=req.id, chunk=index):
            chunk = req.prompt[pos:pos + sc.prefill_chunk]
            n_valid = len(chunk)
            tokens = np.zeros((1, sc.prefill_chunk), np.int32)
            tokens[0, :n_valid] = chunk
            # a copy: the program may start after the host's next change
            table = self._block_tables[req.slot:req.slot + 1].copy()
            if req.prefill_started_at is None:
                req.prefill_started_at = time.monotonic()
                self.timelines.note(req.id, "prefill_started")
            tok = self._fresh_tok = self._rebind(self._call(
                "prefill", self.params, *self.cache, tokens, table,
                np.int32(pos), np.int32(n_valid), *self._draw(),
                *self.family.prefill_extra(req.slot)))[0]
            req.prefill_pos = pos + n_valid
            self.timelines.note(req.id, "prefill_chunk", chunk=index,
                                tokens=n_valid)
            if req.prefill_pos >= len(req.prompt):
                tok.copy_to_host_async()
                self._prefilling.popleft()
                req.state, req.dispatched = RUNNING, 1
                self._lens[req.slot] = len(req.prompt)
                self._first = (req, req.admit_seq, tok)
        return True

    def _first_token(self) -> None:
        """Inside ``serve.emit``: fetch and emit the first token of the last
        chunk dispatched in this tick, unless its request was shed or
        preempted since. The wait is for the chunk alone: the decode step
        dispatched behind it keeps the device busy meanwhile."""
        req, admit_seq, tok = self._first
        self._first = None
        if not self._holds(req, admit_seq):
            return
        with _Phase(self, "prefill.wait"):
            first = int(jax.device_get(tok)[0])
        if self._drained_at is None:
            self._drained_at = self._phase_end
        req.first_token_at = req.last_token_at = time.monotonic()
        self._record_first_token(req)
        self.timelines.note(req.id, "first_token", token=first)
        self._emit(req, first)
        flight.note("serving", "first_token", id=req.id)

    def _record_first_token(self, req: ServingRequest) -> None:
        """``serving_ttft`` and its three parts, which sum to it: the
        admission queue, the FIFO of other prompts' chunks, the request's
        own chunks. One sample each per first token (a preempted request
        reaches a second one; its marks stay those of its first pass)."""
        m = self.metrics
        m.histogram("serving_ttft").record(req.ttft_s)
        m.histogram("serving_queue_wait").record(
            req.admitted_at - req.submitted_at)
        m.histogram("serving_prefill_wait").record(
            req.prefill_started_at - req.admitted_at)
        m.histogram("serving_prefill_run").record(
            req.first_token_at - req.prefill_started_at)

    def _grow_or_preempt(self) -> None:
        """Extend the block table of each request the next decode step will
        run to cover the token that step writes (``_lens`` is advanced when a
        step is dispatched, so it already names that position); when the
        pool is dry, preempt the YOUNGEST live request and retry.

        Preempting youngest (highest ``admit_seq``) keeps the oldest
        request making forward progress, which bounds the scheme: each
        preemption frees at least one page, live requests always hold at
        least one, and the head of the FIFO eventually finishes — no
        livelock. A request can preempt ITSELF (it was the youngest);
        it simply sits out this decode step and re-enters the queue."""
        for req in list(self._slots):
            if req is None or not self._decodes(req):
                continue  # freed or preempted earlier in this pass
            need = self.allocator.pages_needed(int(self._lens[req.slot]) + 1)
            while len(req.pages) < need:
                got = self.allocator.alloc(1)
                if got is not None:
                    self._block_tables[req.slot, len(req.pages)] = got[0]
                    req.pages.extend(got)
                    self.timelines.note(
                        req.id, "page_grow", pages=len(req.pages),
                        occupancy=self.allocator.occupancy())
                    continue
                victim = self._youngest_live()
                if victim is None:
                    break  # unreachable: req itself is live
                self._preempt(victim)
                if victim is req:
                    break

    @staticmethod
    def _decodes(req: ServingRequest) -> bool:
        """Whether the next decode step runs ``req``: it left prefill and
        not all its tokens are dispatched yet. (A row whose last token is in
        flight keeps its slot and pages until that token is emitted.)"""
        return req.state == RUNNING and req.dispatched < req.max_new_tokens

    def _youngest_live(self) -> Optional[ServingRequest]:
        """The most recently admitted request still holding pages."""
        live = [r for r in self._slots if r is not None]
        return max(live, key=lambda r: r.admit_seq, default=None)

    def _preempt(self, req: ServingRequest) -> None:
        """Swap ``req`` out: free its pages and re-enqueue it at the HEAD
        of the admission queue with all generation state reset — decode
        is deterministic (greedy or seeded), so the re-run regenerates
        the same tokens and the caller never observes the eviction beyond
        latency. A step already dispatched still computes its row: that
        token is void (``_InFlight``), and its write lands before whatever
        is dispatched to the freed pages next."""
        tsan.note_access(self, "preempt")
        pages_freed = len(req.pages)
        self.allocator.free(req.pages)
        slot = req.slot
        self._slots[slot] = None
        self._block_tables[slot] = NULL_PAGE
        self._lens[slot] = -1
        if req in self._prefilling:
            self._prefilling.remove(req)
        req.state, req.slot, req.pages = WAITING, -1, []
        req.prefill_pos = 0
        req.tokens, req.dispatched = [], 0
        req.first_token_at = None
        req.last_token_at = None
        req.preemptions += 1
        # head-of-queue re-entry: victims are picked youngest-first, so
        # appendleft keeps the relative admission order among them
        self._waiting.appendleft(req)
        self.metrics.counter("serving_requests_preempted").inc()
        self.timelines.note(req.id, "preempted", pages_freed=pages_freed,
                            occupancy=self.allocator.occupancy(),
                            preemptions=req.preemptions)
        flight.note("serving", "preempt", id=req.id,
                    pages_freed=pages_freed)

    def _shed_expired(self) -> None:
        """Drop every request whose deadline already passed — queued OR
        in-flight — at the decode-tick boundary (the only point where a
        slot can be reclaimed without tearing a step in half). Sheds are
        classified refusals: the caller gets an error response, never
        silence, and the ``serving_deadline_sheds`` counter + the
        ``deadline_shed`` timeline event make every one attributable."""
        now = time.monotonic()

        def expired(r: ServingRequest) -> bool:
            return r.deadline_s is not None and \
                now - r.submitted_at > r.deadline_s

        for req in [r for r in self._waiting if expired(r)]:
            self._waiting.remove(req)
            self._shed(req, now)
        for req in list(self._slots):
            if req is not None and req.state in (PREFILL, RUNNING) \
                    and expired(req):
                self._shed(req, now)

    def _release_slot(self, req: ServingRequest) -> None:
        """Free any slot/pages ``req`` holds (shed/cancel teardown)."""
        if req.slot >= 0:
            self.allocator.free(req.pages)
            slot = req.slot
            self._slots[slot] = None
            self._block_tables[slot] = NULL_PAGE
            self._lens[slot] = -1
            if req in self._prefilling:
                self._prefilling.remove(req)
        req.slot, req.pages = -1, []

    def _shed(self, req: ServingRequest, now: float) -> None:
        """Refuse one expired request, freeing any slot/pages it holds."""
        tsan.note_access(self, "shed")
        age = now - req.submitted_at
        self._release_slot(req)
        req.state = REFUSED
        req.error = (f"deadline_shed: expired {age:.3f}s into a "
                     f"{req.deadline_s:.3f}s deadline")
        req.finished_at = now
        self.metrics.counter("serving_deadline_sheds").inc()
        self.metrics.counter("serving_requests_refused").inc()
        tl = self.timelines.get(req.id)
        if tl is not None:
            tl.note("deadline_shed", age_s=round(age, 4),
                    deadline_s=req.deadline_s,
                    tokens_dropped=len(req.tokens))
            tl.state = "refused"
        flight.note("serving", "deadline_shed", id=req.id,
                    age_s=round(age, 4), deadline_s=req.deadline_s)
        if req.callback:
            req.callback(req)

    def cancel(self, request_id: str) -> bool:
        """Cancel one queued or in-flight request (the ``cancel`` verb —
        hedged dispatch tears down the losing replica's copy with this).
        Runs on the engine thread via the server's control queue, so the
        teardown lands at a step boundary like every other slot
        transition. Returns False when the id is unknown, already
        finished, or already refused."""
        tsan.note_access(self, "cancel")
        rid = str(request_id)
        req = next((r for r in self._waiting if r.id == rid), None)
        if req is not None:
            self._waiting.remove(req)
        else:
            req = next((r for r in self._slots
                        if r is not None and r.id == rid
                        and r.state in (PREFILL, RUNNING)), None)
        if req is None:
            return False
        self._release_slot(req)
        req.state, req.error = REFUSED, "cancelled"
        req.finished_at = time.monotonic()
        self.metrics.counter("serving_requests_refused").inc()
        tl = self.timelines.get(req.id)
        if tl is not None:
            tl.note("refused", why="cancelled")
            tl.state = "refused"
        flight.note("serving", "cancel", id=req.id)
        if req.callback:
            req.callback(req)
        return True

    def _decode_step(self) -> Optional[_InFlight]:
        """Schedule, then dispatch one token for every slot that decodes
        (static batch; masked rows). The host's books move HERE: a row's
        length advances and its token is counted as dispatched; the values
        come back a tick later (``_drain``)."""
        with _Phase(self, "schedule"):
            self._shed_expired()
            if self.serving.lazy_alloc:
                self._grow_or_preempt()
            rows = [r for r in self._slots
                    if r is not None and self._decodes(r)]
        if not rows:
            return None
        with _Phase(self, "decode"):
            # snapshots: ``_lens`` and ``_block_tables`` change while the
            # program that reads these may not have started (and the CPU
            # backend may alias a NumPy buffer instead of copying it)
            slots = [r.slot for r in rows]
            lens = np.full_like(self._lens, -1)
            lens[slots] = self._lens[slots]
            if self.paged_kernel_active:
                self._decoded_lens = lens
            fresh = -1
            if self._first is not None and self._holds(*self._first[:2]):
                fresh = self._first[0].slot
            self.metrics.counter("serving_decode_steps").inc()
            if self._inflight is not None:
                self.metrics.counter("serving_decode_overlapped").inc()
            toks, _, *counters = self._rebind(self._call(
                "decode", self.params, *self.cache, self._tokens,
                np.int32(fresh), self._fresh_tok, self._block_tables.copy(),
                lens, *self._draw()))
            self._tokens = toks
            for leaf in jax.tree.leaves((toks, counters)):
                leaf.copy_to_host_async()   # the fetch will be a wait
            self._lens[slots] += 1          # the step writes position `lens`
            for req in rows:
                req.dispatched += 1
        return _InFlight(toks, counters, [(r, r.admit_seq) for r in rows],
                         lens)

    def _drain(self, step: Optional[_InFlight]) -> None:
        """Fetch the tokens of the step dispatched a tick ago (if one was)
        and emit them, then the first token of a last chunk dispatched in
        this tick (if one was): every latency is stamped here, once the
        value is on the host."""
        if step is not None:
            with _Phase(self, "decode.wait"):
                # a family's step counters ride with the tokens: one
                # device_get
                toks, counters = jax.device_get((step.toks, step.counters))
            self._drained_at = self._phase_end
        with _Phase(self, "emit"):
            if step is not None:
                self._emit_step(step, toks, counters)
            if self._first is not None:
                self._first_token()

    def _emit_step(self, step: _InFlight, toks: np.ndarray,
                   counters: list) -> None:
        """One token for every row of ``step`` that still is what it ran."""
        now = time.monotonic()
        self._record_stats(self.metrics, counters)
        for req, admit_seq in step.rows:
            if not self._holds(req, admit_seq):
                # preempted, cancelled or shed since: no token. Finished
                # since: it emitted its eos while this step ran
                if req.state == FINISHED and req.admit_seq == admit_seq:
                    self.metrics.counter("serving_overrun_rows").inc()
                continue
            self.metrics.histogram("serving_inter_token").record(
                now - req.last_token_at)
            req.last_token_at = now
            self.timelines.note(req.id, "decode_tick",
                                pos=int(step.lens[req.slot]) + 1)
            self._emit(req, int(toks[req.slot]))

    def _emit(self, req: ServingRequest, token: int) -> None:
        """Record one generated token and finish on eos / length."""
        req.tokens.append(token)
        self.metrics.counter("serving_tokens_total").inc()
        if token == self.eos_token_id or \
                len(req.tokens) >= req.max_new_tokens:
            self._finish(req)

    def _finish(self, req: ServingRequest) -> None:
        req.state = FINISHED
        req.finished_at = time.monotonic()
        self.allocator.free(req.pages)
        slot = req.slot
        self._slots[slot] = None
        self._block_tables[slot] = NULL_PAGE
        self._lens[slot] = -1
        self.metrics.counter("serving_requests_completed").inc()
        tl = self.timelines.get(req.id)
        if tl is not None:
            tl.note("finished", new_tokens=len(req.tokens),
                    pages_freed=len(req.pages),
                    occupancy=self.allocator.occupancy())
            tl.state = "finished"
        flight.note("serving", "finish", id=req.id,
                    new_tokens=len(req.tokens))
        if req.callback:
            req.callback(req)

    # ------------------------------------------------------------------ loop
    def step(self) -> bool:
        """One scheduler iteration; True when any device work ran or was
        fetched. The device work of this tick is dispatched BEFORE the
        tokens of the last one are fetched, so the host's book-keeping and
        the fetch's return pass while the device runs; exactly one decode
        step is ever in flight."""
        tsan.note_access(self, "step")
        self.last_tick = {}
        self._drained_at = None
        t_top = self._clock()
        with _tick_span("serve.tick", tick=self.steps):
            with _Phase(self, "admit"):
                self._admit()
            chunk = self._prefill_step()
            drained, self._inflight = self._inflight, self._decode_step()
            if drained is not None or self._first is not None:
                self._drain(drained)
            # the tick's period ends where its step was drained; with none
            # in flight, at a first token's fetch; a tick that only
            # dispatched ends where its dispatch did
            work_end = self._phase_end if self._drained_at is None \
                else self._drained_at
            worked = chunk or drained is not None \
                or self._inflight is not None
            if worked:
                self.steps += 1
            with _Phase(self, "gauges"):
                self._update_gauges()
        self._close_tick(t_top, work_end, worked)
        return worked

    def _close_tick(self, t_top: float, work_end: float,
                    worked: bool) -> None:
        """Finish the last tick's phase seconds; for a tick that worked
        record ``serving_tick``: the period from where the last working
        tick's ended (the top of ``step`` after an idle one) to where this
        one's did — between two drains, what a token costs. The period is
        recorded under ``serving_chunk_tick`` too when the device ran a
        prefill chunk in it (what ``projected_completion_s`` prices a chunk
        at): a chunk runs behind the step that was in flight when it was
        dispatched, so it is inside the period that ends at the NEXT drain.
        First, say so if the tick took many times the recent periods."""
        phases = self.last_tick
        if "prefill.wait" in phases:      # nested: keep the two disjoint
            phases["emit"] -= phases["prefill.wait"]
        phases["tick"] = total = self._phase_end - t_top
        if not worked:
            self._mark = None
            return
        hist = self.metrics.histogram("serving_tick")
        recent = hist.last(SLOW_TICK_WINDOW)
        if len(recent) >= SLOW_TICK_MIN and \
                total > SLOW_TICK_FACTOR * sum(recent) / len(recent):
            logger.warning("slow tick %.2f s: %s", total, ", ".join(
                f"{k} {v:.2f}" for k, v in sorted(
                    phases.items(), key=lambda kv: -kv[1]) if k != "tick"))
        period = work_end - (t_top if self._mark is None else self._mark)
        self._mark = work_end
        # what the tick did is in the phases it went through
        chunk, carried = "prefill" in phases, self._chunk_unpriced
        if "decode.wait" in phases:     # the device is done with all but
            priced, self._chunk_unpriced = carried, chunk   # this tick's
        elif "prefill.wait" in phases:  # ... with this tick's chunk too
            priced, self._chunk_unpriced = carried or chunk, False
        else:               # only dispatched: the chunk's time is to come
            priced, self._chunk_unpriced = chunk, carried or chunk
        hist.record(period)
        if priced:
            self.metrics.histogram("serving_chunk_tick").record(period)

    def has_work(self) -> bool:
        """Anything queued, prefilling, decoding or in flight?"""
        return bool(self._waiting or self._prefilling
                    or self._inflight is not None
                    or any(r is not None for r in self._slots))

    def run_until_drained(self, max_steps: int = 100_000) -> None:
        """Step until every queued request has finished (tests/bench)."""
        steps = 0
        while self.has_work():
            self.step()
            steps += 1
            assert steps < max_steps, "serving loop failed to drain"

    def begin_drain(self) -> None:
        """Stop admitting NEW submissions; everything already queued or in
        flight runs to completion (the graceful-preemption contract)."""
        if not self.draining:
            self.draining = True
            flight.note("serving", "drain",
                        active=sum(r is not None for r in self._slots),
                        queued=len(self._waiting))
            # stamp the preemption onto every live timeline, then spill
            # them into the flight ring: the post-mortem (and the router's
            # merged trace) sees exactly where each request was when the
            # reclaim landed
            for tl in self.timelines.live():
                tl.note("drain")
            self.dump_timelines()
            logger.warning("serving engine draining: finishing %d in-flight "
                           "request(s)", sum(r is not None
                                             for r in self._slots)
                           + len(self._waiting))

    def dump_timelines(self) -> int:
        """Spill every live timeline into the flight ring (crash/drain
        evidence for ``flight.dump``); returns how many were spilled."""
        live = self.timelines.live()
        for tl in live:
            flight.note("serving_timeline", tl.id, state=tl.state,
                        events=tl.events(), dropped=tl.ring.dropped,
                        attribution=tl.attribution())
        return len(live)

    def request_trace(self, rid: str) -> Optional[dict]:
        """The ``trace`` verb's payload for one request id: the bounded
        event timeline + the phase attribution (None when the id is
        unknown or already evicted from the timeline store)."""
        tl = self.timelines.get(rid)
        return tl.to_dict() if tl is not None else None

    # ------------------------------------------------------------- telemetry
    def reset_stats(self) -> None:
        """Zero the serving counters/histograms and restart the throughput
        clock — the bench calls this after its warmup request so compile
        time never pollutes tokens/s or the latency quantiles."""
        for name in ("serving_requests_total", "serving_requests_completed",
                     "serving_requests_refused", "serving_requests_preempted",
                     "serving_tokens_total", "serving_deadline_sheds",
                     "serving_decode_steps", "serving_decode_overlapped",
                     "serving_overrun_rows", "serving_refusals_overloaded",
                     "serving_refusals_unmeetable",
                     "serving_moe_pairs_held_total",
                     "serving_moe_pairs_total", "serving_moe_passes_total"):
            self.metrics.counter(name).reset()
        for name in ("serving_ttft", "serving_inter_token", "serving_tick",
                     "serving_chunk_tick", "serving_queue_wait",
                     "serving_prefill_wait", "serving_prefill_run",
                     "serving_moe_experts_hit",
                     "serving_moe_load_max_over_mean"):
            h = self.metrics.histogram(name)
            h.reset()
            h.total_count = 0
            h.total_sum = 0.0
        self._started_at = time.monotonic()

    def _used_slots(self) -> int:
        """Token positions actually written across live requests."""
        used = int(self._lens[self._lens >= 0].sum())
        used += sum(r.prefill_pos for r in self._prefilling)
        return used

    def _update_gauges(self) -> None:
        self._gauges_current = True
        self.metrics.gauge("serving_queue_depth").set(len(self._waiting))
        self.metrics.gauge("serving_active_requests").set(
            sum(r is not None for r in self._slots))
        self.metrics.gauge("serving_page_occupancy").set(
            self.allocator.occupancy())
        self.metrics.gauge("serving_kv_fragmentation").set(
            self.allocator.internal_fragmentation(self._used_slots()))
        # tokens the running rows hold, a layer of each kind of cache, and
        # the bytes of all cache buffers (fixed when the engine is built)
        full, window = self.family.kv_tokens(self.cfg, self._lens)
        self.metrics.gauge("serving_kv_full_tokens").set(full)
        self.metrics.gauge("serving_kv_window_tokens").set(window)
        self.metrics.gauge("serving_kv_cache_bytes").set(self.cache_bytes)
        if self._decoded_lens is not None:
            # of the page groups in the block table, the share this tick's
            # decode call folded (1.0: every row at the end of its table)
            span, groups = self._programs.kernel.walk_shape
            self.metrics.gauge("serving_page_walk_share").set(
                int(PA.page_groups_walked(self._decoded_lens, span,
                                          groups).sum())
                / (self._decoded_lens.size * groups))

    def serving_snapshot(self) -> dict:
        """One JSON-ready record in the ``SERVING_RECORD_SCHEMA`` shape."""
        m = self.metrics
        wall = max(time.monotonic() - self._started_at, 1e-9)
        ttft = m.histogram("serving_ttft").summary()
        itl = m.histogram("serving_inter_token").summary()
        tokens = m.counter("serving_tokens_total").value
        completed = int(m.counter("serving_requests_completed").value)
        if self._gauges_current:
            gauges = {
                "queue_depth": int(m.gauge("serving_queue_depth").value),
                "active_requests": int(
                    m.gauge("serving_active_requests").value),
                "page_occupancy": float(
                    m.gauge("serving_page_occupancy").value),
                "kv_fragmentation": float(
                    m.gauge("serving_kv_fragmentation").value),
                "scheduler_gauges": "ok",
            }
        else:
            # this engine has never stepped: null + an explicit marker
            # (the hbm_stats convention) instead of a fake-zero occupancy
            gauges = {"queue_depth": None, "active_requests": None,
                      "page_occupancy": None, "kv_fragmentation": None,
                      "scheduler_gauges": "unavailable"}
        def wait_summary(part: str) -> dict:
            h = m.histogram(f"serving_{part}").summary()
            return {k: h.get(k) for k in ("p50", "p95", "count")}

        snap = {
            "ts": time.time(),
            "scope": "serving",
            "schema_version": 2,
            "requests_admitted": int(
                m.counter("serving_requests_total").value
                - m.counter("serving_requests_refused").value),
            "requests_completed": completed,
            "requests_refused": int(
                m.counter("serving_requests_refused").value),
            "requests_preempted": int(
                m.counter("serving_requests_preempted").value),
            "deadline_sheds": int(
                m.counter("serving_deadline_sheds").value),
            "decode_path": ("paged_kernel" if self.paged_kernel_active
                            else "gather"),
            # decode steps dispatched, how many of them while the step
            # before was still unfetched (all but the first of a busy
            # period), and row-steps computed past an eos and dropped
            "decode_steps": int(m.counter("serving_decode_steps").value),
            "decode_overlapped": int(
                m.counter("serving_decode_overlapped").value),
            "overrun_rows": int(m.counter("serving_overrun_rows").value),
            # cache kind -> [pages a fold of the decode kernel takes,
            # copies a cache buffer that fetch them] (kernel path only)
            "kv_folds": {kind: list(shape) for kind, shape
                         in self._programs.kv_folds.items()},
            "serving_state_cache_bytes": self._other_cache_bytes["state"],
            "serving_latent_cache_bytes": self._other_cache_bytes["latent"],
            **gauges,
            "tokens_total": int(tokens),
            "tokens_per_sec": tokens / wall,
            "ttft_p50_s": ttft.get("p50"),
            "ttft_p99_s": ttft.get("p99"),
            "itl_p50_s": itl.get("p50"),
            "itl_p99_s": itl.get("p99"),
            # full windowed summaries: the router pools these
            # count-weighted into its fleet record
            "ttft": ttft,
            "itl": itl,
            # where a first token's wait went: admission queue, the FIFO
            # of other prompts' chunks, the request's own chunks
            "first_token_waits": {
                part: wait_summary(part) for part in
                ("queue_wait", "prefill_wait", "prefill_run")},
            "chips": int(self.n_chips),
            "requests_per_chip": completed / max(self.n_chips, 1),
        }
        # a family with sparse experts: what rode the decode program's
        # outputs to the host with the tokens
        snap.update(self.family.stats_snapshot(m))
        if self.slo is not None:
            snap["slo_attainment"] = self.slo.observe(snap)["attainment"]
        return snap
