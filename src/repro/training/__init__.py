"""Elastic, fault-tolerant training runtime.

The resilient wrapper around ``train/loop.py``: a restartable harness
(:mod:`~repro.training.harness`) that checkpoints through
``checkpoint/manager.py`` and survives injected or real failures, a
deterministic fault-injection layer (:mod:`~repro.training.faults`), an
elastic plan-recovery rung (:mod:`~repro.training.elastic`) that
re-races the mesh-keyed autotune axes when the topology changed under a
restored ``PlanStore``, and a step-time recorder
(:mod:`~repro.training.telemetry`) emitting ``BENCH_train.json`` in the
same trajectory format as ``BENCH_sparsity.json``.
"""
from repro.training.elastic import ElasticPlanReport, recover_plans  # noqa: F401
from repro.training.faults import (  # noqa: F401
    FaultEvent, FaultSchedule, HostLoss, Preemption,
    corrupt_latest_checkpoint)
from repro.training.harness import HarnessConfig, TrainingHarness  # noqa: F401
from repro.training.telemetry import StepTimeRecorder  # noqa: F401
