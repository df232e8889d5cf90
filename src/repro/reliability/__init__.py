"""Reliability & graceful degradation for the EHYB stack.

DESIGN
======

Failure domains and their degradation ladders
---------------------------------------------

The stack has three places where "the fast path" can fail and three
matching recovery ladders.  Every rung is *observable* (a
``ReliabilityWarning`` once per distinct event + a named counter in
``core.counters``) and every ladder terminates in a level that cannot
fail for the same reason the rung above it did.

1. **Kernel dispatch** (``reliability.guard``).  A Pallas kernel can
   fail to lower/compile on a backend, or a backend can have no Pallas
   support at all.  Jitted code cannot ``try/except`` that, so recovery
   lives at host dispatch: ``Plan._raw_apply*`` hands out a
   stable-identity ``_Guard`` that resolves, once per chaos epoch, which
   level of the format's fallback chain actually runs::

       native apply -> lax/gather reference

   The probe (``kernels.ops.backend_supports_pallas`` + a concrete
   zero-vector run of the candidate level) happens on the untraced
   worker thread, so resolution triggered mid-trace stays trace-free.
   Pure-XLA chains skip probing unless chaos is armed — the <5%
   api-overhead budget of the plan layer is untouched.  The autotuner's
   measured pass wraps each candidate the same way: a failing candidate
   is skipped (``tune.candidate_failed``), not fatal.  On a TPU with no
   fault injection armed none of this degrades: a Pallas failure there
   raises, from the probe, the guard and the tuner alike.

2. **Solver iteration** (``core.solver`` + ``api.operator``).  Krylov
   loops fail *numerically*: BiCGStab rho/rhat·v breakdown, CG on an
   indefinite operator, divergence after kernel corruption, stagnation
   at an unreachable tolerance.  In-loop sentinels classify the failure
   into a structured ``SolveResult.status`` (converged / maxiter /
   breakdown / diverged / stagnated) instead of silently returning
   garbage; the host-side escalation ladder in ``solve_operator`` —
   driven by :class:`SolvePolicy` — then restarts from the last finite
   iterate, escalates cg→bicgstab, and finally re-runs on the reference
   CSR matvec that bypasses the planned kernels entirely.

3. **Serving** (``serve.engine``).  Overload and transient apply faults.
   :class:`EnginePolicy` adds a bounded queue (reject-with-reason),
   per-request deadlines enforced at admission and per step,
   retry-with-backoff around the compiled prefill/decode calls, and a
   degraded mode that swaps the sparse pruned head for the dense path
   when the sparse apply keeps failing — admitted requests always finish
   or expire, never hang.

Fault injection (``reliability.chaos``) arms all of the above
deterministically — kernel-site failures by fnmatch pattern, NaN apply
output, latency, serve-call budgets — so each recovery path has a test
that *proves* its fault fired (asserting on ``cfg.injected``) and the
system converged/served correctly anyway.  Chaos entry/exit bumps an
epoch and clears JAX's compile caches so nothing decided or traced under
injection survives it.

Why host-side, not in-graph?  Lowering failures and queue overload are
host phenomena; putting recovery in-graph would make every apply pay
for branching it almost never takes, and could not catch compile-time
faults at all.  The only in-graph machinery is the solver status
tracking, which rides the existing ``while_loop`` carry.
"""

from .chaos import ChaosConfig, ChaosFault, chaos, flood
from .guard import fallback_chain, guarded_apply, reference_apply
from .policy import (EnginePolicy, ReliabilityWarning, SolveFailure,
                     SolveFailureWarning, SolvePolicy)

__all__ = [
    "ChaosConfig",
    "ChaosFault",
    "chaos",
    "flood",
    "fallback_chain",
    "guarded_apply",
    "reference_apply",
    "EnginePolicy",
    "ReliabilityWarning",
    "SolveFailure",
    "SolveFailureWarning",
    "SolvePolicy",
]
