"""Serving engine: prefill + decode steps with continuous batching.

The engine keeps a fixed pool of ``batch`` decode slots (the compiled decode
step has a static batch shape — standard for TPU serving).  Requests queue
up; ALL free slots are prefilled in one compiled full-width prefill per
``step()`` (admitted rows merged into the live state under a mask), and every
step advances all active slots one token with their true per-slot positions —
slots admitted at different times each write their KV-cache entry at their
own index.  Finished slots (EOS or max tokens) are returned and immediately
refillable — the vLLM-style decoupling of request lifetime from batch shape,
minus paging.

Sampling: greedy or temperature (per-request), computed on host from the
device logits of the single new position.

Sparse decode head (``sparse_head_density``): the LM head is the largest
single decode-step matmul (d_model × vocab every token).  When set, the head
weights are magnitude-pruned and served through the Operator API v2 surface
(``repro.api.pruned_linear`` → plan → bind → apply), so decode inherits
whichever format wins for the pruned head's sparsity pattern — the serving-side
integration of the paper's explicit-caching SpMM.  Because every step runs
all slots through ONE decode (and one prefill) program, the concurrent
users' head matvecs coalesce into a single batched SpMM apply of width
``batch`` — the head is planned at that width (``pruned_linear(..., k=)``)
so format selection prices the amortized A-stream, and the batched apply
routes to the SpMM apply that streams each A tile once for the whole
batch.  EHYB-family winners run their permuted-space apply inside
``SparseLinear.__call__`` (permute in, one apply with the ER rows folded
into their owning partitions, un-permute out): activations arrive in feature order and logits
must leave in vocab order every token, so the boundary gathers are inherent
to serving — but everything between them is the same permuted-space fast
path the solver loop runs on, and chained sparse layers can hoist the
boundary too via ``SparseLinear``'s ``to_permuted``/``from_permuted`` space
API.

Weight refreshes (``refresh_sparse_head``): the pruned head's value tables
are passed to the compiled decode/prefill steps as traced arguments, so
pushing updated weights refills the operator through its scatter plan —
same mask, same partitioning, same compiled programs — instead of
re-pruning, re-partitioning, or re-tracing.
"""

from __future__ import annotations

import dataclasses
import time
import warnings
from collections import Counter, deque
from functools import partial
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..core.counters import bump
from ..models import decode_step, init_decode_state, prefill
from ..models.layers import logits_fn
from ..reliability.policy import EnginePolicy, ReliabilityWarning


@dataclasses.dataclass
class Request:
    uid: int
    prompt: np.ndarray                 # (prompt_len,) int32
    max_new_tokens: int = 16
    temperature: float = 0.0
    eos_id: int = -1
    ttl_s: Optional[float] = None      # per-request deadline (None = policy)
    # filled by the engine
    generated: list = dataclasses.field(default_factory=list)
    done: bool = False
    reject_reason: Optional[str] = None   # "queue_full" | "deadline" | None
    _submit_t: Optional[float] = None
    _deadline: Optional[float] = None


class ServeEngine:
    def __init__(self, params, cfg, *, batch: int = 4, max_len: int = 256,
                 max_prompt: int = 64, state_dtype=jnp.float32, seed: int = 0,
                 sparse_head_density: Optional[float] = None,
                 sparse_head_format: str = "auto",
                 sparse_head_mesh=None, sparse_head_axis: str = "data",
                 max_queue: Optional[int] = None,
                 policy: Optional[EnginePolicy] = None,
                 clock: Optional[Callable[[], float]] = None):
        self.params, self.cfg = params, cfg
        self.batch, self.max_len, self.max_prompt = batch, max_len, max_prompt
        self.policy = policy or EnginePolicy()
        if max_queue is not None:
            self.policy = dataclasses.replace(self.policy,
                                              max_queue=max_queue)
        self._clock = clock or time.monotonic
        self.stats: Counter = Counter()
        self.degraded = False
        self.degraded_reason: Optional[str] = None
        self.queue: deque[Request] = deque()
        self.slots: list[Optional[Request]] = [None] * batch
        self.positions = np.zeros(batch, np.int32)
        self.state = init_decode_state(cfg, batch, max_len, state_dtype,
                                       enc_len=max_prompt)
        self.rng = np.random.default_rng(seed)
        self.sparse_head = self._build_sparse_head(
            sparse_head_density, sparse_head_format,
            sparse_head_mesh, sparse_head_axis)
        self._rejit(self.sparse_head)

    def _rejit(self, head):
        """(Re)build the compiled step programs against ``head`` — the
        sparse layer on the healthy path, None in degraded mode."""
        self._decode = jax.jit(partial(self._decode_impl, cfg=self.cfg,
                                       head=head))
        self._prefill = jax.jit(partial(self._prefill_impl, cfg=self.cfg,
                                        head=head))

    def _head_weights(self) -> np.ndarray:
        """The dense (V, d) LM-head weights under the current params."""
        if self.cfg.tie_embeddings:
            return np.asarray(self.params["embed"]["embedding"],
                              dtype=np.float32)             # (V, d)
        return np.asarray(self.params["head"]["w_head"],
                          dtype=np.float32).T               # (d,V) -> (V, d)

    def _build_sparse_head(self, density, fmt, mesh=None, axis="data"):
        """Prune the LM head into the unified-SpMV sparse layer (or None).

        EHYB-family formats serve decode through the fused permuted-space
        pipeline (one kernel launch per token for the head matmul).  A
        ``mesh`` shards the pruned head over ``mesh[axis]`` — vocab-sized
        heads outgrow one device's memory long before the trunk does — and
        the decode-step head matmul pays only the halo exchange; weight
        pushes (``refresh_sparse_head``) still refill in place because the
        sharded tables reach the compiled steps as traced arguments too."""
        if density is None:
            return None
        from ..api import pruned_linear

        # plan at the slot-pool width: every step coalesces the active
        # slots' head matvecs into one (d, batch)-wide SpMM apply, so the
        # format ranking should price the A-stream amortized over it
        return pruned_linear(self._head_weights(), density=density,
                             format=fmt, mesh=mesh, mesh_axis=axis,
                             k=self.batch)

    def _head_obj(self):
        """The sparse head's device container, passed to the compiled steps
        as a *traced* argument (not closure state): value refreshes flow
        into already-compiled decode/prefill programs with no re-trace.
        Degraded mode serves the dense head — no container to pass."""
        if self.sparse_head is None or self.degraded:
            return None
        return self.sparse_head.op.obj

    def refresh_sparse_head(self, params=None):
        """Value-refresh the served pruned head after a weight update.

        The pruning mask, the chosen format's partitioning, and the compiled
        decode/prefill programs all survive: ``SparseLinear.update_values``
        refills the device value tables through the operator's scatter plan,
        and the refreshed container reaches the compiled steps as a traced
        argument on the next ``step()``.  Zero re-partitioning, zero XLA
        recompilation per weight push — the serving-side §6 amortization.
        """
        if params is not None:
            self.params = params
        if self.sparse_head is None:
            return None
        self.sparse_head = self.sparse_head.update_values(self._head_weights())
        return self.sparse_head

    def sparse_head_bytes(self, val_bytes: int = 4):
        """Modeled HBM bytes of one decode-step head matmul (None if the
        dense head is in use) — the serving-side view of the §3.4 traffic
        accounting, fused-ER included via the per-call ("spmv") context."""
        if self.sparse_head is None:
            return None
        return self.sparse_head.bytes_vs_dense(val_bytes)

    # ---- compiled pieces ---------------------------------------------------
    # ``head`` (the SparseLinear, shape/closure metadata) is bound statically
    # via partial; ``head_obj`` (its device value tables) is a TRACED
    # argument, so refresh_sparse_head's refilled containers flow into the
    # compiled programs without re-tracing (closure-captured arrays would be
    # baked in as constants and go stale on refresh).
    @staticmethod
    def _head_logits(params, h, cfg, head, head_obj=None):
        if head is None:
            return logits_fn(params["head"], params["embed"], h, cfg)
        logits = head.apply_with(head_obj, h)
        if cfg.final_softcap:
            c = cfg.final_softcap
            logits = jnp.tanh(logits / c) * c
        return logits

    @staticmethod
    def _decode_impl(params, tokens, state, pos_vec, head_obj, cfg,
                     head=None):
        # true per-slot positions: each slot writes its KV-cache entry (and
        # takes its RoPE angle / causal horizon) at its own index, so slots
        # admitted at different times decode correctly side by side.
        # (An earlier version collapsed to pos_vec.max(), silently writing
        # lagging slots' cache entries at the leading slot's position.)
        h, new_state = decode_step(params, tokens, cfg, state, pos_vec)
        logits = ServeEngine._head_logits(params, h, cfg, head, head_obj)
        return logits[:, 0], new_state

    @staticmethod
    def _prefill_impl(params, batchd, state, admit_mask, head_obj, cfg,
                      head=None):
        """Full-width prefill: every waiting request's row runs through ONE
        compiled program per step and ``admit_mask`` (B,) merges only the
        admitted rows' state back — active slots keep theirs.  All admitted
        prompts' last-position head matvecs coalesce into the one batched
        head apply inside ``_head_logits``."""
        h_last, st = prefill(params, batchd, cfg, state)
        logits = ServeEngine._head_logits(params, h_last, cfg, head, head_obj)

        def merge(old, new):
            # state leaves are (n_units, B, ...): mask broadcast on axis 1
            m = admit_mask.reshape((1, -1) + (1,) * (new.ndim - 2))
            return jnp.where(m, new.astype(old.dtype), old)

        return logits[:, 0], jax.tree.map(merge, state, st)

    # ---- request management -------------------------------------------------
    def submit(self, req: Request) -> bool:
        """Admission control: returns True if queued, False if rejected.

        A rejected request comes back ``done=True`` with
        ``reject_reason="queue_full"`` — callers that ignore the return
        value (the legacy contract) still see a terminal state rather than
        a hang.  Deadlines are stamped here (``req.ttl_s`` falling back to
        the policy's ``default_ttl_s``) and enforced at every step."""
        now = self._clock()
        req._submit_t = now
        ttl = req.ttl_s if req.ttl_s is not None else self.policy.default_ttl_s
        req._deadline = None if ttl is None else now + ttl
        mq = self.policy.max_queue
        if mq is not None and len(self.queue) >= mq:
            req.done = True
            req.reject_reason = "queue_full"
            self.stats["rejected_queue_full"] += 1
            bump("serve.rejected_queue_full")
            return False
        self.queue.append(req)
        self.stats["submitted"] += 1
        return True

    def _expire(self) -> list:
        """Drop queued and active requests whose deadline has passed
        (``reject_reason="deadline"``; an active slot frees immediately —
        its partial ``generated`` tokens stay on the request)."""
        now = self._clock()
        finished = []
        if any(r._deadline is not None and now >= r._deadline
               for r in self.queue):
            keep: deque[Request] = deque()
            while self.queue:
                r = self.queue.popleft()
                if r._deadline is not None and now >= r._deadline:
                    r.done = True
                    r.reject_reason = "deadline"
                    self.stats["expired_queued"] += 1
                    bump("serve.expired")
                    finished.append(r)
                else:
                    keep.append(r)
            self.queue = keep
        for i, r in enumerate(self.slots):
            if (r is not None and r._deadline is not None
                    and now >= r._deadline):
                r.done = True
                r.reject_reason = "deadline"
                self.stats["expired_active"] += 1
                bump("serve.expired")
                finished.append(r)
                self.slots[i] = None
                self.positions[i] = 0
        return finished

    # ---- failure handling ---------------------------------------------------
    def _enter_degraded(self, reason: str) -> None:
        """Swap the sparse pruned head for the dense path: re-jit the step
        programs with ``head=None`` and stop passing the sparse container.
        The sparse layer object is kept — ``restore_sparse_head()`` swaps
        back once the fault clears."""
        self.degraded = True
        self.degraded_reason = reason
        self._rejit(None)
        self.stats["degraded"] += 1
        bump("serve.degraded")
        warnings.warn(
            f"ServeEngine degraded to the dense head after repeated "
            f"sparse-apply failures ({reason})", ReliabilityWarning,
            stacklevel=3)

    def restore_sparse_head(self) -> None:
        """Leave degraded mode (no-op when healthy)."""
        if not self.degraded:
            return
        self.degraded = False
        self.degraded_reason = None
        self._rejit(self.sparse_head)

    def _guarded_call(self, which: str, *args):
        """Run a compiled step with retry/backoff and degraded-mode
        escalation.  ``args`` end with ``head_obj`` by construction of both
        call sites; non-finite logits count as a failure (a silently
        corrupted step poisons every subsequent token)."""
        from ..reliability.chaos import active as _chaos_active

        pol = self.policy
        last: Optional[BaseException] = None
        for phase in range(2):
            fn = self._decode if which == "decode" else self._prefill
            for attempt in range(pol.max_retries + 1):
                try:
                    c = _chaos_active()
                    if c is not None:
                        c.check_serve(sparse_active=args[-1] is not None)
                    out = fn(*args)
                    if not np.isfinite(np.asarray(out[0])).all():
                        raise FloatingPointError(
                            f"{which} step produced non-finite logits")
                    return out
                except Exception as e:   # noqa: BLE001 — any step failure
                    last = e
                    self.stats["retries"] += 1
                    bump("serve.retry")
                    if attempt < pol.max_retries and pol.retry_backoff_s > 0:
                        time.sleep(pol.retry_backoff_s * (2 ** attempt))
            if (phase == 0 and self.sparse_head is not None
                    and not self.degraded):
                self._enter_degraded(f"{type(last).__name__}: {last}")
                args = args[:-1] + (None,)
                continue
            break
        raise last

    def health(self) -> dict:
        """Liveness/degradation snapshot (cheap host state, no device
        sync) — what an ops probe or the bench harness scrapes.  Includes
        the plan/tune cache picture (``plan_cache``): when a persistent
        tune store is active, its disk hit/miss counters show whether this
        engine's pruned-head plan warm-started from disk or paid a cold
        partitioning + tuning pass at startup."""
        from ..api import PLAN_CACHE

        return {
            "queue_depth": len(self.queue),
            "active": sum(r is not None for r in self.slots),
            "batch": self.batch,
            "degraded": self.degraded,
            "degraded_reason": self.degraded_reason,
            "sparse_head": self.sparse_head is not None,
            "max_queue": self.policy.max_queue,
            "stats": dict(self.stats),
            "plan_cache": PLAN_CACHE.stats(),
        }

    def _free_slots(self):
        return [i for i, r in enumerate(self.slots) if r is None]

    def _admit(self):
        """Admit waiting requests into ALL free slots with one coalesced
        full-width prefill (continuous batching: one compiled program per
        step regardless of how many requests arrive, and their head
        matvecs run as a single batched SpMM apply).

        The token sampled from the prefill logits is the request's FIRST
        generated token, so it counts against ``max_new_tokens`` and is
        checked against EOS right here — a request asking for one token
        gets exactly one, and an EOS at prefill never decodes further.
        Returns the list of requests finished at admission."""
        finished = []
        free = self._free_slots()
        while free and self.queue:
            admitted = []
            while free and self.queue:
                admitted.append((free.pop(0), self.queue.popleft()))
            toks = np.zeros((self.batch, self.max_prompt), np.int32)
            mask = np.zeros(self.batch, bool)
            for i, req in admitted:
                prompt = req.prompt[-self.max_prompt:]
                toks[i, :len(prompt)] = prompt
                mask[i] = True
            batchd = {"tokens": jnp.asarray(toks)}
            if self.cfg.family == "encdec":
                batchd["enc_frames"] = jnp.zeros(
                    (self.batch, self.max_prompt, self.cfg.d_model),
                    jnp.dtype(self.cfg.dtype))
            logits, self.state = self._guarded_call(
                "prefill", self.params, batchd, self.state,
                jnp.asarray(mask), self._head_obj())
            logits = np.asarray(logits)
            for i, req in admitted:
                self.slots[i] = req
                self.positions[i] = len(req.prompt[-self.max_prompt:])
                tok = self._sample(logits[i], req)
                req.generated.append(int(tok))
                if (tok == req.eos_id
                        or len(req.generated) >= req.max_new_tokens):
                    req.done = True
                    self.stats["completed"] += 1
                    finished.append(req)
                    self.slots[i] = None
                    self.positions[i] = 0
                    free.append(i)      # reusable within this same pass
        return finished

    def _sample(self, logits: np.ndarray, req: Request) -> int:
        if req.temperature <= 0:
            return int(np.argmax(logits))
        p = np.exp((logits - logits.max()) / req.temperature)
        p = p / p.sum()
        return int(self.rng.choice(len(p), p=p))

    # ---- main loop -----------------------------------------------------------
    def step(self):
        """Expire what's past deadline, admit what fits, then advance every
        active slot one token."""
        finished = self._expire()
        finished.extend(self._admit())
        active = [i for i, r in enumerate(self.slots) if r is not None]
        if not active:
            return finished
        tokens = np.zeros((self.batch, 1), np.int32)
        for i in active:
            tokens[i, 0] = self.slots[i].generated[-1]
        logits, self.state = self._guarded_call(
            "decode", self.params, jnp.asarray(tokens), self.state,
            jnp.asarray(self.positions), self._head_obj())
        logits = np.asarray(logits)
        for i in active:
            req = self.slots[i]
            self.positions[i] += 1
            tok = self._sample(logits[i], req)
            req.generated.append(tok)
            if (tok == req.eos_id or len(req.generated) >= req.max_new_tokens
                    or self.positions[i] >= self.max_len - 1):
                req.done = True
                self.stats["completed"] += 1
                finished.append(req)
                self.slots[i] = None
                self.positions[i] = 0
        return finished

    def run_until_done(self, max_steps: int = 10000):
        out = []
        for _ in range(max_steps):
            out.extend(self.step())
            if not self.queue and all(s is None for s in self.slots):
                break
        return out
