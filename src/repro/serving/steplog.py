"""Step log: one record per dispatched serving step.

Each ``StepRecord`` says what the step carried (rows x bucket token slots,
the real prompt-chunk tokens and decode lanes in them, the requests) and
where the host's time went around it, on ``time.perf_counter()``:

  * ``schedule_s``  ``Scheduler.schedule_step``            (``serve.schedule``)
  * ``build_s``     ``Engine._build_step`` / packed build  (``serve.build``)
  * ``dispatch_s``  launch through the emit-queue put      (``serve.dispatch``)
  * ``t_dispatched`` the moment the step program was enqueued
  * ``t_fetched``   its sampled tokens on the host         (``serve.fetch``,
                    set by the emit worker; sync loop: after
                    ``block_until_ready``)
  * ``apply_s``     token routing, retirement, pool stats  (``serve.apply``)

and, for a model with routed experts, the two routing counters that the
step program returns beside its sampled tokens (``expert_tokens_max``,
``experts_hit``; set by the emit worker in the same host sync).

The names in brackets are the ``jax.profiler.TraceAnnotation`` spans around
the same phases, so a device trace and the log describe one timeline.
Every other field is a host value the loop already holds: writing a record
never waits on the device.

Records go to ``RECENT``, one bounded ring per process. It is process-wide
on purpose, like a metrics registry: an exporter (the benchmark's metric
readers) reads a run's steps after the engine that wrote them is gone.
"""
from __future__ import annotations

import itertools
from collections import deque
from dataclasses import dataclass
from typing import List, Tuple

RING_STEPS = 8192          # several minutes of serving at 10-100 ms steps

ENGINE_IDS = itertools.count()


@dataclass(slots=True)
class StepRecord:
    engine: int                             # Engine.engine_id
    seq: int                                # the engine's dispatch number
    kind: str                               # "decode" | "prefill" | "packed"
    rows: int                               # R: rows of the step's arrays
    bucket: int                             # S: token slots per row
    real_tokens: int                        # prompt-chunk tokens + decode
                                            # lanes
    chunks: Tuple[Tuple[int, int], ...]     # (start, n) per prompt chunk
    decode_ctx: Tuple[int, ...]             # keys each decode lane attends
    req_ids: Tuple[int, ...]                # requests the step carried
    compiled: bool = False                  # the step traced and compiled
                                            # (an AOT miss) on its way out
    t_dispatched: float = 0.0
    schedule_s: float = 0.0
    build_s: float = 0.0
    dispatch_s: float = 0.0
    t_fetched: float = 0.0                  # 0.0 until the tokens arrive
    apply_s: float = 0.0
    # routing counters the step program returns with its tokens (written
    # with t_fetched; 0 for a model without experts, and in the sync loop)
    expert_tokens_max: int = 0              # most tokens one expert took in
                                            # any MoE layer
    experts_hit: float = 0.0                # experts that took any token,
                                            # mean over MoE layers

    @property
    def slots(self) -> int:
        """Token slots the step program ran: rows x bucket."""
        return self.rows * self.bucket

    @property
    def host_s(self) -> float:
        """The serving loop's busy time for this step."""
        return self.schedule_s + self.build_s + self.dispatch_s + self.apply_s


class StepRing(deque):
    """A bounded ``deque`` of ``StepRecord`` with a window query."""

    def between(self, t0: float, t1: float) -> List[StepRecord]:
        """Records dispatched in ``[t0, t1]`` (``perf_counter`` seconds)."""
        return [r for r in tuple(self) if t0 <= r.t_dispatched <= t1]


RECENT = StepRing(maxlen=RING_STEPS)
