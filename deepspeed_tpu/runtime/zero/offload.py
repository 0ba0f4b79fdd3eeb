"""ZeRO-Offload / ZeRO-Infinity host-side optimizer.

Parity (re-designed): the reference keeps fp32 master params + Adam moments in
host DRAM and steps them with AVX ``DeepSpeedCPUAdam`` (stage_1_and_2.py
``cpu_offload``; stage3 + ``swap_tensor`` for NVMe; ``offload_config.py`` knobs).
TPU-native layout:

- the device holds only the bf16/fp16 compute params (sharded);
- the jitted step produces mean grads (+ norm/overflow) and the *host* applies
  the optimizer with the native OpenMP kernels
  (``ops/native/cpu_optimizer.py`` over ``csrc/ds_native.cpp``);
- ``device: nvme`` pushes master+moments to NVMe files, stepped in sub-groups
  through ``PipelinedOptimizerSwapper`` (double-buffered read/step/write);
- ``ratio < 1.0`` implements ZeRO-Offload++-style twin-flow: the largest
  ``1-ratio`` fraction of elements stays on device (stepped inside the jitted
  update) while the rest steps on host — both flows run concurrently.

Leaves are addressed by '/'-joined path keys, the same scheme the checkpoint
layer uses, so state round-trips through save/load unchanged.

The steady-state step is a THREE-STAGE GROUP PIPELINE (docs/TRAINING.md
"Offloaded optimizer pipeline"): host-flow leaves are chunked into groups
(``leaf_groups()``, the same sub-group sizing the NVMe swapper uses) and
``step_groups`` walks them so that while group *g* runs its host kernel,
group *g+1*'s grad D2H fetch is in flight (the engine keeps every group's
transfer queued) and group *g-1*'s updated master is already uploading — with
``PipelinedOptimizerSwapper`` double-buffering the NVMe state reads/writes
underneath, all four resources (device, D2H/H2D link, host CPU, disk)
overlap. The host kernel itself fans leaf chunks across a small worker pool
(``host_workers``): the native OpenMP kernels run under ctypes (GIL
released) and numpy's vectorized inner loops release the GIL too, and every
kernel is elementwise, so chunked execution is bit-identical to serial.
This module is a jaxlint JL007 hot path: it never touches device arrays —
the engine owns the single drain point — so every numpy conversion here
carries an explicit dtype.
"""

from __future__ import annotations

import os
import time
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from deepspeed_tpu.config import OffloadDeviceEnum, OffloadOptimizerConfig
from deepspeed_tpu.monitor.trace import tracer as _tracer
from deepspeed_tpu.ops.native.cpu_optimizer import HostAdam, HostAdagrad, HostLion
from deepspeed_tpu.runtime.swap_tensor import PipelinedOptimizerSwapper
from deepspeed_tpu.utils.logging import logger
from deepspeed_tpu.utils.threads import make_lock


def _host_kernel(optimizer) -> Tuple[str, Any]:
    """Map an engine optimizer instance to its host step kernel."""
    from deepspeed_tpu.ops.adam import FusedAdam
    from deepspeed_tpu.ops.adagrad import DeepSpeedCPUAdagrad
    from deepspeed_tpu.ops.lion import FusedLion
    if isinstance(optimizer, FusedAdam):
        return "adam", HostAdam(lr=optimizer.lr, betas=optimizer.betas,
                                eps=optimizer.eps,
                                weight_decay=optimizer.weight_decay,
                                adamw_mode=optimizer.adam_w_mode,
                                bias_correction=optimizer.bias_correction)
    if isinstance(optimizer, FusedLion):
        return "lion", HostLion(lr=optimizer.lr, betas=optimizer.betas,
                                weight_decay=optimizer.weight_decay)
    if isinstance(optimizer, DeepSpeedCPUAdagrad):
        return "adagrad", HostAdagrad(lr=optimizer.lr, eps=optimizer.eps,
                                      weight_decay=optimizer.weight_decay)
    raise ValueError(
        f"offload_optimizer does not support {type(optimizer).__name__}; "
        "use adam/adamw/adagrad/lion (parity: cpu_offload optimizer check)")


#: state-tree keys per kernel kind (torch-compatible naming, as the device
#: optimizers use)
_STATE_KEYS = {"adam": ("exp_avg", "exp_avg_sq"), "lion": ("exp_avg",),
               "adagrad": ("exp_avg_sq",)}

#: leaves larger than this are split into contiguous chunks across the worker
#: pool; the host kernels are elementwise, so chunking never changes a byte
_CHUNK_ELEMS = 1 << 21


class HostOffloadOptimizer:
    """Owns host-resident master fp32 + optimizer moments for a subset of leaves.

    ``host_names`` (chosen by ``partition_leaves``) step here; the remaining
    leaves keep device state and step inside the jitted update.
    """

    def __init__(self, optimizer, master_leaves: Dict[str, np.ndarray],
                 offload_cfg: OffloadOptimizerConfig):
        self.kind, self.kernel = _host_kernel(optimizer)
        self.cfg = offload_cfg
        # bumped by step()/step_groups() — the serial caller-thread path
        # and the engine's single-worker offload lane are exclusive by
        # engine mode (overlap_step), never concurrent
        self.step_num = 0  # threadlint: guarded-by=none
        self.nvme = offload_cfg.device == OffloadDeviceEnum.nvme
        self._names: List[str] = list(master_leaves)
        self._shapes = {k: v.shape for k, v in master_leaves.items()}
        self.swapper: Optional[PipelinedOptimizerSwapper] = None
        # pipeline groups: buffer_count leaves per group unless group_size
        # overrides — the SAME chunks _nvme_groups expands into swap names,
        # so grad fetch, kernel, and state swap move in lock-step
        per_group = max(1, int(getattr(offload_cfg, "group_size", 0)
                               or offload_cfg.buffer_count))
        self._groups: List[List[str]] = [
            self._names[i:i + per_group]
            for i in range(0, len(self._names), per_group)]
        workers = int(getattr(offload_cfg, "host_workers", 0)) \
            or min(4, os.cpu_count() or 1)
        self._workers = max(1, workers)
        self._kernel_pool = None   # lazy ThreadPoolExecutor
        self._pool_lock = make_lock("offload.pool.create")

        state_keys = _STATE_KEYS[self.kind]
        if not self.nvme:
            # np.array copies: device_get views can be read-only, but the host
            # kernels mutate master in place
            self.master = {k: np.array(v, np.float32) for k, v in master_leaves.items()}
            self.moments = {sk: {k: np.zeros(v.shape, np.float32)
                                 for k, v in master_leaves.items()}
                            for sk in state_keys}
            return

        if not offload_cfg.nvme_path:
            raise ValueError("offload_optimizer.device=nvme requires nvme_path")
        swap_dir = os.path.join(offload_cfg.nvme_path, "zero_stage_offload")
        self.swapper = PipelinedOptimizerSwapper(
            swap_dir,
            pipeline_read=offload_cfg.pipeline_read,
            pipeline_write=offload_cfg.pipeline_write,
            max_pooled_buffers=max(4, 2 * offload_cfg.buffer_count * (1 + len(state_keys))),
            io_retries=offload_cfg.io_retries,
            io_timeout_s=offload_cfg.io_timeout_s)
        self.master = None
        self.moments = None
        for k, v in master_leaves.items():
            self.swapper.register(f"master/{k}", np.ascontiguousarray(v, np.float32))
            for sk in state_keys:
                self.swapper.register(f"{sk}/{k}", np.zeros(v.shape, np.float32))
        logger.info(f"NVMe offload: {len(self._names)} leaves -> {swap_dir}")

    # ------------------------------------------------------------------ #
    # step
    # ------------------------------------------------------------------ #

    def step(self, grads: Dict[str, np.ndarray], lr: float,
             grad_scale: float = 1.0) -> Dict[str, np.ndarray]:
        """SERIAL in-place optimizer step on host leaves; returns updated
        master views. This is the pre-pipeline baseline path
        (``overlap_step: false``): every leaf steps on the caller's thread,
        one after another. ``step_groups`` runs the identical math through
        the overlapped group pipeline.

        ``grad_scale`` folds gradient clipping (and any loss-scale remainder)
        into the host step without an extra pass.
        """
        self.step_num += 1
        state_keys = _STATE_KEYS[self.kind]
        updated: Dict[str, np.ndarray] = {}

        def step_leaf(name: str, p: np.ndarray, moment_arrays: Sequence[np.ndarray]):
            g = np.ascontiguousarray(grads[name].reshape(-1), np.float32)
            if grad_scale != 1.0:
                g = g * np.float32(grad_scale)
            flat = p.reshape(-1)
            self.kernel.step(self.step_num, flat, g,
                             *[m.reshape(-1) for m in moment_arrays], lr=lr)

        if not self.nvme:
            for name in self._names:
                step_leaf(name, self.master[name],
                          [self.moments[sk][name] for sk in state_keys])
                updated[name] = self.master[name]
            return updated

        groups = self._nvme_groups()

        def group_step(views: Dict[str, np.ndarray]):
            for name in {n.split("/", 1)[1] for n in views}:
                p = views[f"master/{name}"]
                step_leaf(name, p, [views[f"{sk}/{name}"] for sk in state_keys])
                updated[name] = np.array(p, np.float32)  # copy before buffer reuse

        self.swapper.run(groups, group_step)
        return updated

    # -- the pipelined step ------------------------------------------------ #

    def leaf_groups(self) -> List[List[str]]:
        """The pipeline's leaf-group partition (host-flow names, in step
        order). The engine derives its per-group flat grad layout from this,
        and ``_nvme_groups`` expands the SAME chunks into swap names."""
        return [list(g) for g in self._groups]

    def _pool(self):
        # double-checked: the serial path and the offload lane can both
        # reach first use — an unguarded lazy init could build two pools
        # and leak the loser's threads
        if self._kernel_pool is None:
            with self._pool_lock:
                if self._kernel_pool is None:
                    from concurrent.futures import ThreadPoolExecutor
                    self._kernel_pool = ThreadPoolExecutor(
                        max_workers=self._workers,
                        thread_name_prefix="dstpu-hostopt")
        return self._kernel_pool

    def _leaf_tasks(self, p: np.ndarray, g: np.ndarray,
                    moments: Sequence[np.ndarray], lr: float):
        """Zero-arg callables stepping contiguous chunks of one flat leaf.
        The kernels are elementwise, so chunk boundaries never change a
        byte vs the serial step."""
        step_num = self.step_num
        n = p.size
        if n <= _CHUNK_ELEMS or self._workers <= 1:
            yield lambda: self.kernel.step(step_num, p, g, *moments, lr=lr)
            return
        for lo in range(0, n, _CHUNK_ELEMS):
            hi = min(n, lo + _CHUNK_ELEMS)
            yield (lambda lo=lo, hi=hi:
                   self.kernel.step(step_num, p[lo:hi], g[lo:hi],
                                    *[m[lo:hi] for m in moments], lr=lr))

    def _run_group_kernel(self, items, lr: float) -> None:
        """Step every leaf of one group; ``items`` is a list of
        ``(p_flat, g_flat, moment_flats)``. Chunks fan across the worker
        pool (ctypes/OpenMP and numpy inner loops both release the GIL).
        Each chunk records a span on ITS worker's track (threads
        ``dstpu-hostopt_*``), so the fan-out is visible on the timeline."""
        tasks = [t for p, g, ms in items for t in self._leaf_tasks(p, g, ms, lr)]
        if self._workers <= 1 or len(tasks) <= 1:
            for t in tasks:
                t()
            return
        futs = [self._pool().submit(self._traced_task, t) for t in tasks]
        for f in futs:
            f.result()

    @staticmethod
    def _traced_task(task) -> None:
        with _tracer.span("train/offload/kernel_chunk"):
            task()

    def step_groups(self, grad_views_for: Callable[[int], Dict[str, np.ndarray]],
                    lr: float, grad_scale: float = 1.0,
                    on_group_done: Optional[Callable] = None,
                    record: Optional[Callable] = None) -> None:
        """Pipelined host step over ``leaf_groups()``.

        ``grad_views_for(g)`` returns ``{leaf name: fp32 1-D grad}`` for
        group *g*, blocking only until THAT group's grads are host-resident
        (the engine keeps every group's D2H queued, so group g+1's fetch is
        in flight while group g's kernel runs). ``on_group_done(g, masters)``
        fires the moment group *g*'s update lands; ``masters`` maps leaf name
        -> fp32 array safe to hand to the upload thread (RAM mode: the stable
        master storage; NVMe mode: a copy made before the pooled swap buffer
        is recycled). ``record(phase, seconds)`` accumulates 'fetch' /
        'kernel' / 'swap' phase timings.

        Identical math to :meth:`step` — the kernels are elementwise and the
        group/chunk walk covers the same leaves with the same ``step_num``.
        """
        perf = time.perf_counter
        rec = record if record is not None else (lambda phase, s: None)
        done = on_group_done if on_group_done is not None else (lambda g, m: None)
        if not self._groups:
            return
        self.step_num += 1
        state_keys = _STATE_KEYS[self.kind]

        def leaf_item(p, moments, g):
            g = np.ascontiguousarray(g.reshape(-1), np.float32)
            if grad_scale != 1.0:
                g = g * np.float32(grad_scale)
            return (p.reshape(-1), g, [m.reshape(-1) for m in moments])

        if not self.nvme:
            for gi, names in enumerate(self._groups):
                t0 = perf()
                grads = grad_views_for(gi)
                t1 = perf()
                self._run_group_kernel(
                    [leaf_item(self.master[n],
                               [self.moments[sk][n] for sk in state_keys],
                               grads[n]) for n in names], lr)
                t2 = perf()
                rec("fetch", t1 - t0)
                rec("kernel", t2 - t1)
                if _tracer.enabled:
                    _tracer.add("train/offload/fetch", t0, t1,
                                lane="train/offload", group=gi)
                    _tracer.add("train/offload/kernel", t1, t2,
                                lane="train/offload", group=gi)
                done(gi, {n: self.master[n] for n in names})
            return

        # NVMe: the double-buffered state swapper composes underneath — its
        # sub-groups are the SAME leaf groups, so while group g's kernel
        # runs, g+1's state read AND grad D2H are both in flight and g-1's
        # state write drains on the third AIO handle.
        counter = {"g": 0, "inside": 0.0}
        t_run0 = perf()

        def step_fn(views: Dict[str, np.ndarray]):
            gi = counter["g"]
            counter["g"] += 1
            names = self._groups[gi]
            t0 = perf()
            grads = grad_views_for(gi)
            t1 = perf()
            self._run_group_kernel(
                [leaf_item(views[f"master/{n}"],
                           [views[f"{sk}/{n}"] for sk in state_keys],
                           grads[n]) for n in names], lr)
            # copy out before the pooled swap buffer is reused downstream
            masters = {n: np.array(views[f"master/{n}"], np.float32)
                       for n in names}
            t2 = perf()
            rec("fetch", t1 - t0)
            rec("kernel", t2 - t1)
            if _tracer.enabled:
                _tracer.add("train/offload/fetch", t0, t1,
                            lane="train/offload", group=gi)
                _tracer.add("train/offload/kernel", t1, t2,
                            lane="train/offload", group=gi)
            counter["inside"] += t2 - t0
            done(gi, masters)

        self.swapper.run(self._nvme_groups(), step_fn)
        rec("swap", (perf() - t_run0) - counter["inside"])

    def _nvme_groups(self) -> List[List[str]]:
        """Sub-groups of swap names — the pipeline's ``leaf_groups()``
        expanded to master+moment keys (parity: stage3 sub_group_size
        slicing for the optimizer swapper)."""
        state_keys = _STATE_KEYS[self.kind]
        groups = []
        for chunk in self._groups:
            group = []
            for name in chunk:
                group.append(f"master/{name}")
                group.extend(f"{sk}/{name}" for sk in state_keys)
            groups.append(group)
        return groups

    # ------------------------------------------------------------------ #
    # state materialisation (checkpoint save/load)
    # ------------------------------------------------------------------ #

    def state_leaves(self) -> Tuple[Dict[str, np.ndarray],
                                    Dict[str, Dict[str, np.ndarray]]]:
        """(master, moments) in one pass — one NVMe read of the swap state."""
        state_keys = _STATE_KEYS[self.kind]
        if not self.nvme:
            # frozen COPIES, not the live arrays: host Adam mutates master/
            # moments in place, and callers hand these leaves to background
            # checkpoint writers (or a snapshot/restore) that must not
            # observe the next step's values
            return ({k: np.array(v, np.float32) for k, v in self.master.items()},
                    {sk: {k: np.array(v, np.float32)
                          for k, v in self.moments[sk].items()}
                     for sk in state_keys})
        all_t = self.swapper.read_all()
        master = {k[len("master/"):]: v for k, v in all_t.items()
                  if k.startswith("master/")}
        moments = {sk: {k[len(sk) + 1:]: v for k, v in all_t.items()
                        if k.startswith(sk + "/")} for sk in state_keys}
        return master, moments

    def master_leaves(self) -> Dict[str, np.ndarray]:
        return self.state_leaves()[0]

    def moment_leaves(self) -> Dict[str, Dict[str, np.ndarray]]:
        return self.state_leaves()[1]

    def load_master_leaves(self, leaves: Dict[str, np.ndarray]) -> None:
        for k, v in leaves.items():
            if k not in self._names:
                continue
            if self.nvme:
                self.swapper.write(f"master/{k}", np.asarray(v, np.float32))
            else:
                self.master[k][...] = np.asarray(v, np.float32).reshape(self._shapes[k])

    def load_moment_leaves(self, moments: Dict[str, Dict[str, np.ndarray]],
                           step_num: Optional[int] = None) -> None:
        for sk, leaves in moments.items():
            if sk not in _STATE_KEYS[self.kind]:
                continue
            for k, v in leaves.items():
                if k not in self._names:
                    continue
                if self.nvme:
                    self.swapper.write(f"{sk}/{k}", np.asarray(v, np.float32))
                else:
                    self.moments[sk][k][...] = np.asarray(v, np.float32).reshape(self._shapes[k])
        if step_num is not None:
            self.step_num = int(step_num)

    def close(self):
        with self._pool_lock:
            pool, self._kernel_pool = self._kernel_pool, None
        if pool is not None:
            pool.shutdown(wait=True)
        if self.swapper is not None:
            self.swapper.close()


def partition_leaves(leaves: Dict[str, np.ndarray], ratio: float
                     ) -> Tuple[List[str], List[str]]:
    """Split leaf names into (host, device) sets by element count.

    ``ratio`` is the fraction of optimizer elements stepped on host
    (``offload_optimizer.ratio``, the ZeRO-Offload++ twin-flow knob). Largest
    leaves stay on device first — they benefit most from MXU-side updates.
    """
    if ratio >= 1.0:
        return list(leaves), []
    if ratio <= 0.0:
        return [], list(leaves)
    total = sum(int(np.prod(v.shape)) for v in leaves.values())
    budget = ratio * total
    # smallest-first go to host until the budget is filled
    order = sorted(leaves, key=lambda k: int(np.prod(leaves[k].shape)))
    host, device, used = [], [], 0
    for name in order:
        n = int(np.prod(leaves[name].shape))
        if used + n <= budget or not host:
            host.append(name)
            used += n
        else:
            device.append(name)
    return host, device
