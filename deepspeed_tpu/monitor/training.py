"""Training step-loop observability: per-step timing/queue counters.

The async training loop (``runtime/data_pipeline.py`` + the engine's deferred
metric drain) overlaps four things per global step — dequeuing the next
staged batch, the fused step's dispatch, the host-side staging of batch k+N
(in the PrefetchLoader producer), and the drain of step k-1's metrics.
Whether that overlap happens is invisible from steps/sec alone (a loop can
hit its throughput while secretly serialising), so ``train_batch`` accounts
every step's wall time into the phases below and this module turns the
totals into ``monitor/`` events (``MonitorMaster.write_events``
``(name, value, step)`` shape — the same contract ``PipelineStats`` and
``PrefixCacheStats`` follow on the serving side).

Every stat class here aggregates the SAME measured intervals the span
tracer records as timeline spans (``train/step/*``, ``train/offload/*``,
``ckpt/*`` — ``monitor/trace.py``, docs/OBSERVABILITY.md): one set of
``perf_counter`` pairs per site feeds both the window aggregate and the
Perfetto track, so a dashboard number always has a matching span to zoom
into.

Phase semantics (per step):

- ``enqueue_wait``: host time blocked on the prefetch queue. Unlike every
  other phase this one is ALLOWED to grow: it is where the host waits when
  the device is the bottleneck, which is the healthy steady state. It is a
  problem only when ``queue_depth`` is simultaneously 0 — then the producer
  (collate + device_put), not the device, is what the host is waiting for.
- ``host_build``: synchronous staging on the caller's thread — collate,
  curriculum truncation, PLD injection, the sharded device_put. Near-zero
  when prefetching (the producer does it); the whole per-step tax when not.
- ``dispatch``: host time enqueueing the fused train step (jax async
  dispatch — NOT device execution time).
- ``drain``: host time materialising DEFERRED metrics (step k-1's
  loss/lr/grad_norm, fetched one step late while step k runs). Under
  ``wall_clock_breakdown`` this becomes the step's full sync.
- ``queue_depth``: prefetch queue occupancy at dequeue time. Persistently 0
  with prefetch enabled means the producer is the bottleneck; persistently
  full means the device is (the healthy steady state).
"""

from __future__ import annotations

from collections import deque
from dataclasses import dataclass, field
from typing import Deque, List

from deepspeed_tpu.monitor.monitor import Event

#: step_wall_ms window — bounded so a long-lived engine (record_step fires on
#: EVERY train_batch, forever) cannot grow host memory without bound; the
#: serving twin clears its list per run, the training loop has no run scope
WALL_WINDOW = 512


@dataclass
class TrainPipelineStats:
    """Aggregate counters for one engine's training loop (cumulative;
    ``reset()`` between measurement windows)."""

    steps: int = 0
    enqueue_wait_ms: float = 0.0
    host_build_ms: float = 0.0
    dispatch_ms: float = 0.0
    drain_ms: float = 0.0
    queue_depth_sum: int = 0
    prefetched_steps: int = 0        # steps fed by an already-staged batch
    #: wall times (ms) of the most recent ``WALL_WINDOW`` steps — a bounded
    #: p50/p99 latency window (``list(...)`` it for np.percentile)
    step_wall_ms: Deque[float] = field(
        default_factory=lambda: deque(maxlen=WALL_WINDOW))

    def record_step(self, wait_s: float, build_s: float, dispatch_s: float,
                    drain_s: float, wall_s: float, queue_depth: int = 0,
                    prefetched: bool = False) -> None:
        self.steps += 1
        self.enqueue_wait_ms += 1e3 * wait_s
        self.host_build_ms += 1e3 * build_s
        self.dispatch_ms += 1e3 * dispatch_s
        self.drain_ms += 1e3 * drain_s
        self.queue_depth_sum += int(queue_depth)
        self.prefetched_steps += int(bool(prefetched))
        self.step_wall_ms.append(1e3 * wall_s)

    def reset(self) -> None:
        self.steps = 0
        self.enqueue_wait_ms = 0.0
        self.host_build_ms = 0.0
        self.dispatch_ms = 0.0
        self.drain_ms = 0.0
        self.queue_depth_sum = 0
        self.prefetched_steps = 0
        self.step_wall_ms = deque(maxlen=WALL_WINDOW)

    def events(self, step: int = 0) -> List[Event]:
        """Monitor-ready ``(name, value, step)`` tuples; per-step averages so
        dashboards stay comparable across runs of different lengths."""
        n = max(1, self.steps)
        return [
            ("train/pipeline/steps", float(self.steps), step),
            ("train/pipeline/enqueue_wait_ms_per_step",
             self.enqueue_wait_ms / n, step),
            ("train/pipeline/host_build_ms_per_step",
             self.host_build_ms / n, step),
            ("train/pipeline/dispatch_ms_per_step",
             self.dispatch_ms / n, step),
            ("train/pipeline/drain_ms_per_step", self.drain_ms / n, step),
            ("train/pipeline/queue_depth", self.queue_depth_sum / n, step),
            ("train/pipeline/prefetched_fraction",
             self.prefetched_steps / n, step),
        ]


@dataclass
class CheckpointStats:
    """Rolling-checkpoint observability (``checkpoint/rolling.py`` +
    ``save_checkpoint``; emitted at print boundaries beside
    TrainPipelineStats as ``train/ckpt/*``).

    Phase semantics (per save):

    - ``snapshot``: device->host materialisation of the state flats — the
      ONLY phase on the step loop's critical path when the async engine
      writes. Growing snapshot time means the state grew or the transfer
      link is contended, not that the disk is slow.
    - ``commit``: writer drain + manifest + ``latest`` flip, on the
      background committer (async engine) or inline (native engine).
    - ``backpressure``: host time the step loop blocked because
      ``rolling.max_pending`` snapshots were still uncommitted — nonzero
      means the disk/writers cannot keep up with the cadence (raise
      ``every_n_steps``, add writers, or accept the stall).
    - ``queue_depth``: checkpoint-engine writer queue occupancy sampled at
      each save submit.
    - ``retries``: cumulative bounded-retry count from the writer path
      (``CheckpointEngine.retries``).
    - ``pruned``: rolling tags deleted by retention.
    """

    saves: int = 0
    snapshot_ms: float = 0.0
    commit_ms: float = 0.0
    backpressure_ms: float = 0.0
    queue_depth_sum: int = 0
    retries: int = 0
    pruned: int = 0

    def record_save(self, snapshot_s: float, backpressure_s: float = 0.0,
                    queue_depth: int = 0) -> None:
        self.saves += 1
        self.snapshot_ms += 1e3 * snapshot_s
        self.backpressure_ms += 1e3 * backpressure_s
        self.queue_depth_sum += int(queue_depth)

    def record_commit(self, commit_s: float, pruned: int = 0) -> None:
        self.commit_ms += 1e3 * commit_s
        self.pruned += int(pruned)

    def reset(self) -> None:
        self.saves = 0
        self.snapshot_ms = 0.0
        self.commit_ms = 0.0
        self.backpressure_ms = 0.0
        self.queue_depth_sum = 0
        self.retries = 0
        self.pruned = 0

    def events(self, step: int = 0) -> List[Event]:
        n = max(1, self.saves)
        return [
            ("train/ckpt/saves", float(self.saves), step),
            ("train/ckpt/snapshot_ms_per_save", self.snapshot_ms / n, step),
            ("train/ckpt/commit_ms_per_save", self.commit_ms / n, step),
            ("train/ckpt/backpressure_ms_per_save",
             self.backpressure_ms / n, step),
            ("train/ckpt/writer_queue_depth", self.queue_depth_sum / n, step),
            ("train/ckpt/retries", float(self.retries), step),
            ("train/ckpt/pruned_tags", float(self.pruned), step),
        ]


@dataclass
class OffloadPipelineStats:
    """Phase counters for the offloaded optimizer's fetch/step/upload group
    pipeline (``runtime/zero/offload.py step_groups`` + the engine's upload
    lane; docs/TRAINING.md "Offloaded optimizer pipeline").

    Phase semantics (accumulated over every group of every step):

    - ``fetch``: host time blocked draining a group's grads D2H. Small in
      steady state — every group's transfer is queued up front, so group g's
      drain overlaps group g-1's kernel. Growing fetch with upload near zero
      means the link, not the host kernel, is the bottleneck.
    - ``kernel``: host optimizer wall time (chunked across the worker pool).
      The phase the other three exist to hide.
    - ``upload``: upload-lane wall time (concat + cast + async device_put of
      a finished group's master). Runs on its own worker, overlapping later
      groups' kernels.
    - ``swap``: NVMe-mode only — time the state swapper's ``run`` spent
      outside the step function (read waits, write drains). The pure IO cost
      of the nvme tier over the cpu tier.
    - ``upload_depth``: pending uploads observed at each group completion;
      persistently high means H2D (or the merge) is the bottleneck.
    """

    steps: int = 0
    groups: int = 0
    fetch_ms: float = 0.0
    kernel_ms: float = 0.0
    upload_ms: float = 0.0
    swap_ms: float = 0.0
    upload_depth_sum: int = 0

    #: phase name -> attribute, the ``add(phase, seconds)`` contract shared
    #: with ``HostOffloadOptimizer.step_groups``'s ``record`` callback
    _PHASES = {"fetch": "fetch_ms", "kernel": "kernel_ms",
               "upload": "upload_ms", "swap": "swap_ms"}

    def add(self, phase: str, seconds: float) -> None:
        attr = self._PHASES[phase]
        setattr(self, attr, getattr(self, attr) + 1e3 * seconds)

    def record_step(self, groups: int, depth_sum: int = 0) -> None:
        self.steps += 1
        self.groups += int(groups)
        self.upload_depth_sum += int(depth_sum)

    def reset(self) -> None:
        self.steps = 0
        self.groups = 0
        self.fetch_ms = 0.0
        self.kernel_ms = 0.0
        self.upload_ms = 0.0
        self.swap_ms = 0.0
        self.upload_depth_sum = 0

    def events(self, step: int = 0) -> List[Event]:
        n = max(1, self.steps)
        g = max(1, self.groups)
        return [
            ("train/offload/steps", float(self.steps), step),
            ("train/offload/groups_per_step", self.groups / n, step),
            ("train/offload/fetch_ms_per_group", self.fetch_ms / g, step),
            ("train/offload/kernel_ms_per_group", self.kernel_ms / g, step),
            ("train/offload/upload_ms_per_group", self.upload_ms / g, step),
            ("train/offload/swap_ms_per_step", self.swap_ms / n, step),
            ("train/offload/upload_depth", self.upload_depth_sum / g, step),
        ]


@dataclass
class RolloutStats:
    """Colocated-rollout loop counters (``runtime/colocated.py``;
    docs/TRAINING.md "Colocated rollout"). Aggregated from the SAME
    ``perf_counter`` stamp pairs that become the
    ``train/rollout/{sync,swap,generate}`` tracer spans (PR 7
    stats-equals-spans discipline) — one ``record_*`` call per span, so
    every dashboard aggregate has a matching timeline span to zoom into.

    Phase semantics (per rollout round):

    - ``sync``: the WeightBridge's device-resident reshard — one jitted
      program from the training engine's sharded optimizer view to the
      serving engine's layout (dispatch + ``block_until_ready``). Moves
      ``sync_bytes`` of serving-layout weights per round without a host
      round-trip; compare against ``ckpt/*`` spans for the disk-path cost
      this replaces.
    - ``swap``: in-place rebind of the live serving engine's weights at a
      run boundary — quiesce (recompute-preempt / shed) of in-flight
      decode, weight-version bump, prefix-cache flush. ``preempted`` and
      ``shed`` count the quiesce casualties; on a drained engine both
      are 0 and the swap is O(validation).
    - ``generate``: the serving leg of the round — submitting prompts and
      draining rollouts that feed the next train batch.
    """

    rounds: int = 0
    sync_ms: float = 0.0
    swap_ms: float = 0.0
    generate_ms: float = 0.0
    sync_bytes: int = 0
    preempted: int = 0
    shed: int = 0
    requests: int = 0
    tokens: int = 0
    weight_version: int = 0

    def record_sync(self, seconds: float, *, nbytes: int = 0) -> None:
        self.rounds += 1
        self.sync_ms += 1e3 * seconds
        self.sync_bytes = int(nbytes)

    def record_swap(self, seconds: float, *, version: int = 0,
                    preempted: int = 0, shed: int = 0) -> None:
        self.swap_ms += 1e3 * seconds
        self.weight_version = int(version)
        self.preempted += int(preempted)
        self.shed += int(shed)

    def record_generate(self, seconds: float, *, requests: int = 0,
                        tokens: int = 0) -> None:
        self.generate_ms += 1e3 * seconds
        self.requests += int(requests)
        self.tokens += int(tokens)

    def reset(self) -> None:
        self.rounds = 0
        self.sync_ms = 0.0
        self.swap_ms = 0.0
        self.generate_ms = 0.0
        self.sync_bytes = 0
        self.preempted = 0
        self.shed = 0
        self.requests = 0
        self.tokens = 0
        self.weight_version = 0

    def events(self, step: int = 0) -> List[Event]:
        n = max(1, self.rounds)
        return [
            ("train/rollout/rounds", float(self.rounds), step),
            ("train/rollout/sync_ms_per_round", self.sync_ms / n, step),
            ("train/rollout/swap_ms_per_round", self.swap_ms / n, step),
            ("train/rollout/generate_ms_per_round", self.generate_ms / n, step),
            ("train/rollout/sync_bytes", float(self.sync_bytes), step),
            ("train/rollout/preempted", float(self.preempted), step),
            ("train/rollout/shed", float(self.shed), step),
            ("train/rollout/requests", float(self.requests), step),
            ("train/rollout/tokens", float(self.tokens), step),
            ("train/rollout/weight_version", float(self.weight_version), step),
        ]
