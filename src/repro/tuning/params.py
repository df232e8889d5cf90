"""Tunable kernel parameters: the declared, bounded search space.

PRs 1–9 hardcoded three machine-sensitive constants deep inside the
execution stack:

* ``_GATHER_BUDGET`` (``kernels/ehyb_spmv.py``) — the VMEM bytes of packed
  value/column tiles one grid step of the Pallas kernel streams in and
  gathers against, i.e. how many partitions share a grid step;
* ``_RHS_CHUNK`` (``kernels/ehyb_spmv.py``) — rhs columns per grid step of
  a multi-rhs (SpMM) apply;
* ``n_buckets`` (``core/ehyb.build_buckets``) — how many width classes the
  bucketed format splits its partition tiles into (more buckets = less
  padding, more kernel launches).

The right values depend on the accelerator (VMEM size, vector width, launch
overhead), which is exactly what a hand-picked constant cannot know.  This
module promotes them to first-class *tuned parameters*: a frozen, hashable
:class:`TunedParams` that rides :class:`repro.api.ExecutionConfig` into the
plan identity (changing a tuned value changes the execution token and
therefore the compiled program), plus a declared candidate grid
(:data:`SEARCH_SPACE`) that the measured tuner sweeps and the on-disk store
persists per machine.  Bounds are validated at construction so a corrupted
store entry can never smuggle an absurd tile size into a kernel.
"""

from __future__ import annotations

import dataclasses
import itertools
from typing import Dict, Iterator, Optional, Tuple


@dataclasses.dataclass(frozen=True)
class ParamSpec:
    """One tunable parameter: default, sweep candidates, hard bounds."""

    name: str
    default: int
    candidates: Tuple[int, ...]       # the measured sweep's grid
    lo: int                           # inclusive hard bounds (validation)
    hi: int
    description: str = ""

    def validate(self, value: int) -> int:
        if not isinstance(value, int) or isinstance(value, bool) or \
                not (self.lo <= value <= self.hi):
            raise ValueError(
                f"tuned parameter {self.name}={value!r} outside its "
                f"declared bounds [{self.lo}, {self.hi}]")
        return value


#: The declared search space.  ``candidates`` are what the measured sweep
#: tries; ``lo``/``hi`` are the validation envelope for values arriving from
#: a store file or a caller.
SEARCH_SPACE: Dict[str, ParamSpec] = {
    "gather_budget": ParamSpec(
        "gather_budget", default=4 * 1024 * 1024,
        candidates=(1 << 20, 2 << 20, 4 << 20, 8 << 20),
        lo=64 * 1024, hi=64 * 1024 * 1024,
        description="VMEM bytes of packed value/column tiles per grid "
                    "step (sizes the partitions one Pallas step handles)"),
    "rhs_chunk": ParamSpec(
        "rhs_chunk", default=16, candidates=(8, 16, 32),
        lo=1, hi=256,
        description="rhs columns per grid step of the Pallas SpMM "
                    "apply"),
    "n_buckets": ParamSpec(
        "n_buckets", default=4, candidates=(2, 4, 8),
        lo=1, hi=16,
        description="width classes for the bucketed format's partition "
                    "tiles (one pallas/jnp stage per class)"),
}


@dataclasses.dataclass(frozen=True)
class TunedParams:
    """A concrete assignment of every tunable kernel parameter.

    Hashable and bounded — a :class:`~repro.api.ExecutionConfig` carries one
    (or ``None`` for "resolve via store/sweep/defaults") and folds
    :meth:`token` into the plan identity, so two plans tuned differently
    never share a cache slot, a jit cache entry, or a compiled kernel.
    """

    gather_budget: int = SEARCH_SPACE["gather_budget"].default
    rhs_chunk: int = SEARCH_SPACE["rhs_chunk"].default
    n_buckets: int = SEARCH_SPACE["n_buckets"].default

    def __post_init__(self):
        for name, spec in SEARCH_SPACE.items():
            spec.validate(getattr(self, name))

    # -- identity ----------------------------------------------------------

    def token(self) -> tuple:
        """Hashable identity (sorted name/value pairs — the execution-token
        member and the static aux the packed device container carries)."""
        return tuple(sorted(
            (name, getattr(self, name)) for name in SEARCH_SPACE))

    # -- persistence -------------------------------------------------------

    def to_dict(self) -> Dict[str, int]:
        return {name: getattr(self, name) for name in SEARCH_SPACE}

    @classmethod
    def from_dict(cls, d: dict) -> "TunedParams":
        """Rehydrate from a store payload; unknown keys are ignored (a newer
        library may have dropped a knob), missing keys take defaults, and
        out-of-bounds values raise — the store treats that as corruption."""
        return cls(**{name: int(d[name]) for name in SEARCH_SPACE
                      if name in d})


#: The hand-derived constants PRs 1–9 shipped, as one canonical object.
DEFAULT_PARAMS = TunedParams()


def sweep_grid(format: str, k: int = 1) -> Iterator[TunedParams]:
    """Candidate :class:`TunedParams` the measured sweep tries for a format.

    Only the knobs a format actually reads are swept (the rest stay at
    their defaults, keeping the grid small and the plan identity honest):

    * ``ehyb_packed`` — ``gather_budget`` (partitions per Pallas grid
      step), crossed with ``rhs_chunk`` when the plan's rhs width ``k`` is a
      batch;
    * ``ehyb_bucketed`` — ``n_buckets`` (tile structure);
    * everything else — the defaults only (nothing to tune yet).
    """
    if format == "ehyb_packed":
        rhs = SEARCH_SPACE["rhs_chunk"].candidates if k >= 2 \
            else (SEARCH_SPACE["rhs_chunk"].default,)
        for gb, rc in itertools.product(
                SEARCH_SPACE["gather_budget"].candidates, rhs):
            yield TunedParams(gather_budget=gb, rhs_chunk=rc)
    elif format == "ehyb_bucketed":
        for nb in SEARCH_SPACE["n_buckets"].candidates:
            yield TunedParams(n_buckets=nb)
    else:
        yield DEFAULT_PARAMS


def resolve(tuned: Optional["TunedParams"]) -> "TunedParams":
    """``None`` -> the library defaults (one shared instance)."""
    return DEFAULT_PARAMS if tuned is None else tuned
