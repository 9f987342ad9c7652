"""Population-aware detector serving: continuous batching over chip
committees.

Serving the IRC detector means answering each request with a calibrated
uncertainty drawn from a committee of sampled virtual dies — not a single
chip's lucky draw.  This engine grows the slot-wave idea of
`repro.serve.engine.ServeEngine` into a detector service:

  submit / result        bounded async request queue with admission control
                         (`ServeQueueFull` once `max_queue` is reached);
                         requests may arrive from any thread
  wave scheduler         pending images batch into waves of `batch_slots`
                         lanes; one wave = ONE jitted dispatch of
                         `repro.mc.committee_wave_forward`, with the next
                         wave dispatched to the device while the host
                         decodes the current one (the PR 6 double-buffer)
  DetectionResponse      boxes decoded from the committee-MEAN prediction
                         plus population mean/std/quantile confidence over
                         the per-chip detection scores

Key discipline (repro.analysis rule KEY004): the engine holds only a root
key; request `rid`'s committee is keyed by the STATELESS coordinate
`fold_in(root, rid)`, never by a split chain threaded through engine state.
A request's committee draws are therefore independent of which requests
preceded it or share its wave, and bit-identical to
`run_mc_detector(fold_in(root, rid), ...)` at the same chip ids — pinned by
tests/test_serve_detector.py.
"""
from __future__ import annotations

import dataclasses
import threading
import time
from collections import deque
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import nonideal as ni
from repro.mc.detector_mc import committee_wave_forward, detector_planes
from repro.mc.stats import StreamingMoments, DEFAULT_QUANTILES
from repro.obs import LatencyTracker, PhaseTimer, RunLog, as_runlog
from repro.train.det_loss import decode_detections

# Short waves pad up to `batch_slots` lanes with this reserved request id so
# every wave runs the ONE compiled executable; `submit` rejects user ids at
# or above it.  Pad lanes are discarded on the host.
PAD_REQUEST_ID = 0x7FFFFFFF


class ServeQueueFull(RuntimeError):
    """Admission control: the bounded request queue is at capacity."""


@dataclasses.dataclass(frozen=True)
class Detection:
    """One decoded box: (cx, cy, w, h) as image fractions, committee-mean
    confidence `score`, and the predicted class index."""
    box: Tuple[float, float, float, float]
    score: float
    cls: int


@dataclasses.dataclass
class DetectionResponse:
    """One request's answer from its chip committee.

    detections  boxes decoded (conf threshold + per-class NMS) from the
                committee-MEAN head prediction
    confidence  population statistics of the per-chip top detection score:
                {count, mean, std, q05..q95} — the committee's calibrated
                uncertainty (std/quantile spread = how much this request's
                answer depends on the die it lands on)
    queue_s     submit -> response wall time (queue wait + wave execution)
    committee   raw per-chip head predictions [chips, gh, gw, ho], kept only
                when the engine was built with `keep_committee=True`
    """
    request_id: int
    detections: List[Detection]
    confidence: Dict[str, float]
    wave: int
    queue_s: float
    committee: Optional[np.ndarray] = None


@dataclasses.dataclass
class _Pending:
    """Queue entry: request payload plus its completion handshake."""
    request_id: int
    image: np.ndarray
    t_submit: float
    done: threading.Event
    response: Optional[DetectionResponse] = None


class DetectorServeEngine:
    """Continuously-batched committee inference over a fixed serving fleet.

    The fleet is the first `committee` chips of the MC key stream; the
    per-layer group planes are hoisted ONCE at construction
    (`detector_planes`), so a wave dispatch carries only images and request
    keys.  Drive it synchronously (`serve_batch`, or `submit` +
    `process_pending` + `result`) or start the background scheduler thread
    (`start`/`stop`) and submit from anywhere.

    `params` should carry calibrated stem-BN running stats
    (`det.calibrate_bn`) — eval-mode normalization uses them.
    """

    def __init__(self, det, params, *, committee: int = 4,
                 batch_slots: int = 4, max_queue: int = 64,
                 cfg_ni: ni.NonidealConfig = ni.NonidealConfig.all(),
                 sa_extra: float = 0.0, seed: int = 0,
                 conf_thresh: float = 0.1, nms_thresh: float = 0.45,
                 quantiles: Tuple[float, ...] = DEFAULT_QUANTILES,
                 use_kernel: Optional[bool] = None,
                 kernel_impl: str = "pallas",
                 keep_committee: bool = False,
                 obs: Optional[RunLog] = None,
                 device=None):
        self.det = det
        self.params = params
        self.committee = committee
        self.slots = batch_slots
        self.max_queue = max_queue
        self.cfg_ni = cfg_ni
        self.sa_extra = sa_extra
        self.conf_thresh = conf_thresh
        self.nms_thresh = nms_thresh
        self.quantiles = quantiles
        self.use_kernel = use_kernel
        self.kernel_impl = kernel_impl
        # repro.device backend the committee chips are sampled from (None:
        # analytic) — e.g. get_device_model("measured", t_days=30) serves
        # the fleet as it will behave after a month in the field
        self.device = device
        self.keep_committee = keep_committee
        # Root key only; request keys are the STABLE coordinates
        # fold_in(root, request_id) — never a split chain through engine
        # state (repro.analysis rule KEY004), so a request's draws cannot
        # depend on serving history.
        self._root_key = jax.random.PRNGKey(seed)
        self._pad_key = jax.random.fold_in(self._root_key, PAD_REQUEST_ID)
        self._chip_ids = jnp.arange(committee, dtype=jnp.uint32)
        self._planes, self._meta = detector_planes(det, params)
        self._lock = threading.Lock()
        self._work = threading.Condition(self._lock)
        self._queue: deque = deque()
        self._pending: Dict[int, _Pending] = {}
        self._next_id = 0
        self._waves = 0
        self._stop_flag = False
        self._thread: Optional[threading.Thread] = None
        self.obs = as_runlog(obs)
        self.wave_timer = PhaseTimer("serve_wave", unit="requests")
        self.dev_timer = PhaseTimer("serve_device", unit="requests")
        self.host_timer = PhaseTimer("serve_host", unit="requests")
        self.queue_latency = LatencyTracker()

    # ------------------------------------------------------------ requests

    def submit(self, image, request_id: Optional[int] = None) -> int:
        """Enqueue one [H, W, 3] image; returns its request id.

        Raises `ServeQueueFull` when `max_queue` requests are already
        waiting (admission control — the caller sheds load or retries), and
        `ValueError` on ids outside [0, PAD_REQUEST_ID).  Thread-safe.
        """
        img = np.asarray(image, np.float32)
        with self._work:
            if len(self._queue) >= self.max_queue:
                raise ServeQueueFull(
                    f"queue at capacity ({self.max_queue} pending)")
            rid = self._next_id if request_id is None else int(request_id)
            if not 0 <= rid < PAD_REQUEST_ID:
                raise ValueError(f"request_id {rid} outside "
                                 f"[0, {PAD_REQUEST_ID})")
            if rid in self._pending:
                raise ValueError(f"request_id {rid} already in flight")
            self._next_id = max(self._next_id, rid + 1)
            p = _Pending(request_id=rid, image=img,
                         t_submit=time.perf_counter(),
                         done=threading.Event())
            self._queue.append(p)
            self._pending[rid] = p
            self._work.notify()
        return rid

    def result(self, request_id: int,
               timeout: Optional[float] = None) -> DetectionResponse:
        """Block until `request_id`'s response is ready and return it."""
        with self._lock:
            p = self._pending[request_id]
        if not p.done.wait(timeout):
            raise TimeoutError(f"request {request_id} not served within "
                               f"{timeout}s")
        with self._lock:
            self._pending.pop(request_id, None)
        assert p.response is not None
        return p.response

    def serve_batch(self, images) -> List[DetectionResponse]:
        """Submit a batch and drain it synchronously; responses in order."""
        rids = [self.submit(img) for img in images]
        self.process_pending()
        return [self.result(rid) for rid in rids]

    # ------------------------------------------------------------ scheduler

    def start(self) -> None:
        """Start the background scheduler thread (continuous batching:
        waves form whenever requests are pending)."""
        if self._thread is not None:
            return
        self._stop_flag = False
        self._thread = threading.Thread(target=self._serve_loop, daemon=True)
        self._thread.start()

    def stop(self) -> None:
        """Stop the scheduler thread after it finishes the current wave."""
        with self._work:
            self._stop_flag = True
            self._work.notify_all()
        if self._thread is not None:
            self._thread.join()
            self._thread = None

    def process_pending(self) -> int:
        """Drain the queue in the caller's thread; returns requests served.

        Waves are double-buffered like the MC chunk loop: wave k+1 is
        dispatched to the device BEFORE wave k's host-side decode, so the
        device computes the next committee while the host builds responses.
        """
        return self._drain(block=False)

    def _serve_loop(self) -> None:
        while not self._stop_flag:
            self._drain(block=True)

    def _collect_wave(self, block: bool) -> List[_Pending]:
        with self._work:
            while block and not self._queue and not self._stop_flag:
                self._work.wait()
            n = min(self.slots, len(self._queue))
            return [self._queue.popleft() for _ in range(n)]

    def _drain(self, *, block: bool) -> int:
        wave = self._collect_wave(block)
        if not wave:
            return 0
        inflight = None
        served = 0
        while wave:
            with self.wave_timer.lap(items=len(wave)):
                with self.dev_timer.lap(items=len(wave)):
                    # first wave of a drain dispatches inside the lap so the
                    # timers attribute trace/compile to the compile lap
                    if inflight is None:
                        inflight = self._dispatch(wave)
                    preds = np.asarray(jax.block_until_ready(inflight))
                nxt = self._collect_wave(block=False)
                # double buffer: next wave on device DURING host decode
                inflight = self._dispatch(nxt) if nxt else None
                with self.host_timer.lap(items=len(wave),
                                         span="repro.serve.decode"):
                    responses = self._complete(wave, preds)
            self._log_wave(responses)
            served += len(wave)
            wave = nxt
        return served

    # ------------------------------------------------------------ wave body

    def _dispatch(self, wave: List[_Pending]):
        """One wave -> one async device dispatch of the committee forward."""
        n_pad = self.slots - len(wave)
        imgs = [p.image for p in wave] + [np.zeros_like(wave[0].image)] * n_pad
        keys = [jax.random.fold_in(self._root_key, p.request_id)
                for p in wave] + [self._pad_key] * n_pad
        return committee_wave_forward(
            self.params, jnp.asarray(np.stack(imgs)), jnp.stack(keys),
            self._chip_ids, self._planes, det_cfg=self.det.cfg,
            spec=self.det.spec, cfg_ni=self.cfg_ni, sa_extra=self.sa_extra,
            meta=self._meta, use_kernel=self.use_kernel,
            kernel_impl=self.kernel_impl, device=self.device)

    def _complete(self, wave: List[_Pending],
                  preds: np.ndarray) -> List[DetectionResponse]:
        """Decode each live lane's committee into its response."""
        cfg = self.det.cfg
        self._waves += 1
        responses = []
        for i, p in enumerate(wave):
            committee = preds[i]                      # [chips, gh, gw, ho]
            boxes, scores, classes = decode_detections(
                committee.mean(axis=0), cfg.n_anchors, cfg.n_classes,
                self.conf_thresh, self.nms_thresh)
            per_chip = np.array([self._top_score(chip) for chip in committee],
                                np.float32)
            moments = StreamingMoments(self.quantiles)
            moments.update(jnp.asarray(per_chip))
            queue_s = time.perf_counter() - p.t_submit
            p.response = DetectionResponse(
                request_id=p.request_id,
                detections=[Detection(box=tuple(float(v) for v in b),
                                      score=float(s), cls=int(c))
                            for b, s, c in zip(boxes, scores, classes)],
                confidence=moments.summary(), wave=self._waves,
                queue_s=queue_s,
                committee=committee.copy() if self.keep_committee else None)
            self.queue_latency.add(queue_s)
            responses.append(p.response)
            p.done.set()
        return responses

    def _top_score(self, chip_pred: np.ndarray) -> float:
        """One chip's scalar vote: its top decoded detection score (0.0 when
        the chip detects nothing above the confidence threshold)."""
        cfg = self.det.cfg
        _, scores, _ = decode_detections(chip_pred, cfg.n_anchors,
                                         cfg.n_classes, self.conf_thresh,
                                         self.nms_thresh)
        return float(scores[0]) if scores.size else 0.0

    def _log_wave(self, responses: List[DetectionResponse]) -> None:
        self.obs.log_event(
            "serve_wave", wave=self._waves, requests=len(responses),
            committee=self.committee, wall_s=self.wave_timer.last_s,
            device_s=self.dev_timer.last_s, host_s=self.host_timer.last_s,
            queue_s=[r.queue_s for r in responses],
            requests_per_sec=len(responses) / max(self.wave_timer.last_s,
                                                  1e-9))

    # ------------------------------------------------------------ telemetry

    def stats(self) -> Dict[str, Dict[str, float]]:
        """Phase summaries (first-wave compile split from steady-state
        requests/sec) plus queue-latency percentiles.  All are host-clock
        times: "device" is how long the host was blocked waiting for each
        wave's committee forward, not the device's own compute time;
        "host" is the wave decode (`_complete`, the profiler span
        `repro.serve.decode`)."""
        return {"wave": self.wave_timer.summary(),
                "device": self.dev_timer.summary(),
                "host": self.host_timer.summary(),
                "queue_latency": self.queue_latency.summary()}

    def log_stats(self) -> None:
        """Emit the phase/latency summaries as RunLog events."""
        self.wave_timer.log_to(self.obs, waves=self._waves)
        self.dev_timer.log_to(self.obs, waves=self._waves)
        self.host_timer.log_to(self.obs, waves=self._waves)
        self.obs.log_event("serve_latency", **self.queue_latency.summary())
