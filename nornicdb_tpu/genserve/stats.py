"""Generation-engine metric families (``nornicdb_genserve_*``).

Registered at import time (idempotent by-name resolution, same pattern as
serving/stats.py) so the docs/observability.md catalog — a tested
contract — renders these families in every process that serves traffic,
whether or not a GenerationEngine was ever constructed.  server/http.py
imports this module for exactly that reason.
"""

from __future__ import annotations

import threading
import weakref

from nornicdb_tpu.telemetry.metrics import REGISTRY as _REGISTRY

# generation requests waiting for admission into the running batch; a
# persistently deep queue means max_seqs / pool_pages are undersized for
# the offered load (sheds_total{reason="queue_full"} is the overflow)
QUEUE_DEPTH = _REGISTRY.gauge(
    "nornicdb_genserve_queue_depth",
    "Generation requests queued for admission into the running batch",
)
# the next three are read off the live engines at scrape time (the collect
# hook at the end of this file): no step sets them
RUNNING_SEQS = _REGISTRY.gauge(
    "nornicdb_genserve_running_seqs",
    "Sequences currently resident in the continuous decode batch",
)
# allocated / usable physical pages: sustained ~1.0 with evictions rising
# means the pool thrashes — grow pool_pages or lower max_seqs
PAGE_POOL_UTIL = _REGISTRY.gauge(
    "nornicdb_genserve_page_pool_utilization",
    "Fraction of the paged-KV pool's usable pages currently allocated",
)
PREFILL_HIST = _REGISTRY.histogram(
    "nornicdb_genserve_prefill_seconds",
    "Per-chunk prompt prefill latency (one interleaved chunk)",
    buckets=(0.001, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5),
)
DECODE_HIST = _REGISTRY.histogram(
    "nornicdb_genserve_decode_step_seconds",
    "Batched decode-step latency (one token for every running sequence)",
    buckets=(0.001, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5),
)
# admission-control + lifecycle sheds by reason: queue_full at submit,
# deadline pre-dispatch/at the caller, pool_exhausted when a lone request
# cannot fit, device when fallback="fail" and the backend is degraded,
# predicted_deadline when the cost model shed the request at submit
SHEDS = _REGISTRY.counter(
    "nornicdb_genserve_sheds_total",
    "Generation requests shed by admission control or deadline",
    labels=("reason",),
)
for _reason in ("queue_full", "deadline", "pool_exhausted", "device",
                "predicted_deadline"):
    SHEDS.labels(_reason)  # eager cells: render at 0
# rate() of this counter is the aggregate tokens/s the engine sustains
TOKENS = _REGISTRY.counter(
    "nornicdb_genserve_generated_tokens_total",
    "Tokens generated across all sequences (rate = aggregate tokens/s)",
)
EVICTIONS = _REGISTRY.counter(
    "nornicdb_genserve_evictions_total",
    "Sequences evicted from the running batch on page-pool pressure "
    "(requeued and re-prefilled)",
)
REQUESTS = _REGISTRY.counter(
    "nornicdb_genserve_requests_total",
    "Generation requests by terminal outcome",
    labels=("outcome",),
)
for _outcome in ("ok", "shed", "error"):
    REQUESTS.labels(_outcome)
# prefill tokens split by pass: "first" is the initial prompt pass,
# "re" is tokens re-prefilled after a youngest-eviction requeue — the
# bench's prefill-throughput number must use "first" only (counting
# re-prefill inflates it with work the pool pressure forced, not work
# the offered load asked for)
PREFILL_TOKENS = _REGISTRY.counter(
    "nornicdb_genserve_prefill_tokens_total",
    "Prompt tokens prefilled, split by pass (first = initial prompt "
    "pass, re = re-prefill after eviction requeue)",
    labels=("pass",),
)
for _pass in ("first", "re"):
    PREFILL_TOKENS.labels(_pass)
# shared-prefix KV cache: a hit means one whole prompt-prefix page was
# adopted from the pool instead of re-prefilled; hits * page_size is the
# prefill work the cache elided (ttft saved is roughly proportional)
PREFIX_HITS = _REGISTRY.counter(
    "nornicdb_genserve_prefix_hits_total",
    "Shared-prefix cache hits (whole KV pages adopted at admission "
    "instead of prefilled)",
)
PREFIX_PAGES = _REGISTRY.gauge(
    "nornicdb_genserve_prefix_pages",
    "KV pages currently indexed by the shared-prefix cache (resident "
    "and adoptable, whether or not any sequence holds them)",
)
# routed experts (a decoder family that has them; 0 forever otherwise):
# top-k assignments that fell on the experts THIS process holds — under
# expert parallelism its share of the routed work; rate() against
# generated + prefilled tokens shows routing drifting off this rank
EXPERT_ASSIGNMENTS = _REGISTRY.counter(
    "nornicdb_genserve_expert_assignments_total",
    "Top-k expert assignments that fell on experts held by this process "
    "(summed over the expert layers of every fused step)",
)
# the last fused step's imbalance: rows of its fullest held expert, summed
# over the expert layers (even routing = assignments / held experts)
EXPERT_ROWS_MAX = _REGISTRY.gauge(
    "nornicdb_genserve_expert_rows_max",
    "Rows the fullest held expert got in the last fused step, summed over "
    "the expert layers",
)
# zero-compute (identity) experts (a family whose router scores them; 0
# forever otherwise): top-k choices that cost no FLOPs and, across
# expert-parallel ranks, no exchange; rate() against routed rows x top-k is
# the share of a row's expert work that is free
ZERO_EXPERT_ASSIGNMENTS = _REGISTRY.counter(
    "nornicdb_genserve_zero_expert_assignments_total",
    "Top-k expert choices that fell on zero-compute (identity) experts "
    "(summed over the expert branches of every fused step)",
)
# attention over live lengths (a family whose step walks its lanes' page
# tables only as far as the longest live lane reaches; 0 forever
# otherwise): rate(walked) / rate(table) is the share of a full-table
# step's gathers and scores that the steps still do
ATTN_SLOTS_WALKED = _REGISTRY.counter(
    "nornicdb_genserve_attn_slots_walked_total",
    "Cache slots the fused steps' attention blocks gathered and scored "
    "(summed over lanes and attention blocks)",
)
ATTN_SLOTS_TABLE = _REGISTRY.counter(
    "nornicdb_genserve_attn_slots_table_total",
    "Cache slots the same lanes' whole page tables hold (what a step that "
    "gathered every page of every lane would have walked)",
)
# a run of pages that every live decode lane's table begins with (lanes
# seated behind one cached prefix hold the same physical pages) is gathered
# once a walk for all of them (models/kv_walk.py; 0 forever for a family
# with a walk of its own): rate() against decode steps is the length, in
# pages, of what a step's lanes have in common
SHARED_RUN_PAGES = _REGISTRY.counter(
    "nornicdb_genserve_shared_run_pages_total",
    "KV pages of the run every live decode lane's table begins with, "
    "gathered once a walk for all lanes (one layer, summed over fused steps)",
)
# page kinds (a decoder family whose layers keep more than one kind of cache
# state, window layers beside full ones; 0 forever otherwise).  Pages of a
# kind with a horizon that lanes let go WHILE THEY LIVED, as their windows
# moved past them: rate() against generated + prefilled tokens / page_size
# is the share of a long lane's pages that the pool gets back early
PAGES_RELEASED = _REGISTRY.counter(
    "nornicdb_genserve_pages_released_total",
    "Holds on KV pages that running sequences let go as their attention "
    "window moved past them, by page kind",
    labels=("kind",),
)
# what the fused steps' attention walked of each kind's tables, and what
# their lanes' live queries could see: walked / held is how much more than
# the live, in-window pages a step gathers (1.0 = nothing more)
ATTN_PAGES_WALKED = _REGISTRY.counter(
    "nornicdb_genserve_attn_pages_walked_total",
    "KV pages the fused steps' attention blocks gathered and scored "
    "(summed over lanes and the kind's layers), by page kind",
    labels=("kind",),
)
ATTN_PAGES_HELD = _REGISTRY.counter(
    "nornicdb_genserve_attn_pages_held_total",
    "KV pages that hold a slot the same lanes' live queries may see, by "
    "page kind",
    labels=("kind",),
)
for _kind in ("full", "window"):
    PAGES_RELEASED.labels(_kind)
    ATTN_PAGES_WALKED.labels(_kind)
    ATTN_PAGES_HELD.labels(_kind)

# a state kind (a decoder family with state-space layers: one slot of
# recurrent state a lane beside the K/V pages; 0 forever otherwise).  The
# prefix cache keeps SNAPSHOTS of a prefilling lane's state at chunk ends
# that fall on a page boundary: taken = slots written as one, hit =
# admissions that began from one (a prefix hit ends where a snapshot
# stands), dropped = snapshots reclaimed, oldest idle first, for a newer
# one or for a lane.  rate(dropped) near rate(taken) with few hits means
# state_slots is too small for the prompts' shared prefixes
STATE_SNAPSHOTS = _REGISTRY.counter(
    "nornicdb_genserve_state_snapshots_total",
    "Snapshots of a lane's recurrent state in the prefix cache, by event "
    "(taken, hit, dropped)",
    labels=("event",),
)

# the scheduler thread's turn (docs/observability.md "The scheduler's
# turn"): cumulative seconds of each phase of the cycle, the
# ``genserve.turn.*`` stages' own durations.  read = blocked on the device;
# the other four are the host's work, and where their rate() nears a
# step's length the host sets the pace
TURN_PHASE = _REGISTRY.counter(
    "nornicdb_genserve_turn_phase_seconds_total",
    "Seconds of the scheduler thread's cycle, by phase (admit, plan, "
    "dispatch, read = blocked on the device, deliver)",
    labels=("phase",),
)
# runnable and not running: a turn less its blocked read less the thread's
# own CPU seconds.  High beside a short plan/deliver = the thread waits for
# the GIL behind the HTTP threads (or for a core), not for its own Python
HOST_OFFCPU = _REGISTRY.counter(
    "nornicdb_genserve_host_offcpu_seconds_total",
    "Seconds of the scheduler's turns, blocked reads apart, in which its "
    "thread was not on a CPU",
)
# rate() against the steps is the share of steps the chip waited for
LATE_DISPATCHES = _REGISTRY.counter(
    "nornicdb_genserve_late_dispatches_total",
    "Fused steps dispatched after the step before them had already "
    "finished on the device (the chip waited for the host)",
)
# from a token's delivery on the scheduler thread to its consumer coming
# back for the next (decoded, serialised, written): summed over the tokens
# of the streams that have ended
STREAM_LAG = _REGISTRY.counter(
    "nornicdb_genserve_stream_lag_seconds_total",
    "Seconds streamed tokens took from the scheduler to their consumer's "
    "next read, summed over the streams that ended",
)
# GenStats field -> the cell it is rendered into
_FROM_STATS = {
    "admit_seconds": TURN_PHASE.labels("admit"),
    "plan_seconds": TURN_PHASE.labels("plan"),
    "dispatch_seconds": TURN_PHASE.labels("dispatch"),
    "read_wait_seconds": TURN_PHASE.labels("read"),
    "deliver_seconds": TURN_PHASE.labels("deliver"),
    "host_offcpu_seconds": HOST_OFFCPU.labels(),
    "late_dispatches": LATE_DISPATCHES.labels(),
    "stream_lag_seconds": STREAM_LAG.labels(),
    "state_snapshots_taken": STATE_SNAPSHOTS.labels("taken"),
    "state_snapshot_hits": STATE_SNAPSHOTS.labels("hit"),
    "state_snapshots_dropped": STATE_SNAPSHOTS.labels("dropped"),
}
# engine -> what the last scrape read of its stats (weak: an engine that is
# gone leaves the totals where they stand)
_ENGINES: "weakref.WeakKeyDictionary" = weakref.WeakKeyDictionary()
_ENGINES_LOCK = threading.Lock()


def track(engine) -> None:
    """A GenerationEngine whose ``stats`` and pool the scrape reads."""
    with _ENGINES_LOCK:
        _ENGINES[engine] = {}


def _collect() -> None:
    """Scrape time: the families above move by what each engine's
    ``GenStats`` moved since the last scrape, and the three pool gauges
    are summed over the engines that are serving.  Nothing on the
    scheduler's path touches a cell of these."""
    with _ENGINES_LOCK:
        engines = list(_ENGINES.items())
    running = used = usable = prefix = 0
    for engine, seen in engines:
        for field, cell in _FROM_STATS.items():
            now = getattr(engine.stats, field)
            cell.inc(now - seen.get(field, 0))
            seen[field] = now
        if engine.running:
            seqs, taken, pages, cached = engine._pool_gauges()
            running, used = running + seqs, used + taken
            usable, prefix = usable + pages, prefix + cached
    RUNNING_SEQS.set(running)
    PAGE_POOL_UTIL.set(used / max(1, usable))
    PREFIX_PAGES.set(prefix)


_REGISTRY.collect_hook("genserve", _collect)
