"""Resilience: preemption-safe, self-healing training runs.

On TPU pods preemption, coordinator hangs, and flaky slices are the
NORMAL operating regime, not the exception, and a run babysat by an
ad-hoc shell watchdog leaves no auditable trail (a failure log
overwritten by the next attempt is the canonical loss). This
package moves fault handling from the queue script into the framework,
the position "Collective Communication for 100k+ GPUs" (PAPERS.md)
argues is mandatory at scale:

  signals.py    SIGTERM/preemption-notice guard: final synchronous
                checkpoint + clean exit with a distinct resumable code
  heartbeat.py  step-progress heartbeat file + in-process hang
                watchdog (a stalled collective aborts with diagnostics
                instead of hanging the allocation)
  retry.py      bounded retry/backoff with deterministic jitter, used
                for jax.distributed.initialize, checkpoint restore,
                and shared-filesystem dataset reads
  supervisor.py bounded restart-with-resume process supervisor
                (``python -m tpu_hpc.resilience.supervisor -- <cmd>``)
                replacing the shell watchdog; attempt-unique log
                paths, failure dumps are never overwritten
  faults.py     deterministic fault injection (kill-at-step,
                preempt-at-step, stall, corrupt/bitflip-ckpt-write,
                nan-loss, grad-spike, straggler delay, plus the
                stage-scoped kill/nan/straggler kinds the MPMD
                pipeline runtime consumes) so all of the above is
                testable on CPU
  guard.py      numeric-health guard: per-step health vector
                classification (healthy/spike/poisoned) with
                skip-batch and rollback-to-last-good actions, plus
                the persisted skip windows that fast-forward the
                data stream past poisoned batches

Everything here is stdlib-only and import-cheap: the supervisor must
start (and restart a dead run) without touching jax (guard.py's and
faults.py's jax-touching closures import it lazily).
"""
from tpu_hpc.resilience.faults import FaultPlan, fault_plan_from_env  # noqa: F401
from tpu_hpc.resilience.guard import (  # noqa: F401
    GuardError,
    GuardPolicy,
    StepVerdict,
)
from tpu_hpc.resilience.heartbeat import HangWatchdog, Heartbeat  # noqa: F401
from tpu_hpc.resilience.retry import backoff_delays, retry_call  # noqa: F401
from tpu_hpc.resilience.signals import (  # noqa: F401
    EXIT_HANG,
    EXIT_RESUMABLE,
    EXIT_ROLLBACK,
    PreemptionGuard,
    exit_code_for,
)
