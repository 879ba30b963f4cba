"""Batched serving engine with continuous batching + BPCC coded head.

Slot-based continuous batching: a fixed decode batch of ``n_slots``
sequences; finished slots are immediately refilled by prefilling the next
queued request into the slot (per-slot cache insertion on the batch axis).
Greedy sampling.

BPCC integration (the paper's technique on the serving hot path):

  * when ``cfg.coded`` is set, the LM-head matvec — the single largest
    decode-time matrix–vector product — runs through the block-coded
    CodedLinear: any ``coded_parity`` model-shards may be erased (straggling
    / dead) and the logits remain exact;
  * the per-step erasure mask comes from a pluggable ``mask_fn`` — wire it
    to ``repro.runtime.health.HealthMonitor.straggler_mask`` to drop shards
    the monitor flags, without stalling the batch (the paper's "don't wait
    for stragglers", bulk-synchronous flavour);
  * alternatively ``latency_fn`` supplies per-shard latency estimates and
    the engine consumes the FIRST DECODABLE SUBSET of shard outputs each
    step: the ``n_data`` earliest shards survive, the ``n_parity`` laggards
    are dropped (``first_decodable_mask``), and the mask-keyed
    ``DecoderCache`` decodes whichever subset that step produced — a
    per-step-varying mask costs one table gather, never an SVD;
  * with a ``core.adaptive.ParityController`` the parity level itself is
    picked per step from the recent straggler posterior (DESIGN.md §8):
    a healthy step drops no shards (best conditioning, no wasted work),
    while shards the posterior flags as persistent stragglers are dropped
    up to the code's parity budget.

Host-sync discipline (the decode hot loop): greedy argmax runs ON DEVICE
inside the jitted step, ``last_tok`` stays device-resident and feeds the
next step without a round-trip, and exactly ONE device->host transfer per
step (the [n_slots] int32 token vector) serves the bookkeeping (EOS, output
accumulation).  The seed engine pulled the full [n_slots, vocab] fp32
logits to host and argmax'd in numpy — at 100k+ vocab that transfer was
the per-token critical path.  The decode programs donate the KV cache:
a step stores each slot's new key/value rows in place in the buffers it
was handed, so no step copies the cache.

Fused macro-steps (DESIGN.md §14): with ``macro_steps=K_max > 1`` the
engine can decode K steps per launch — a jitted ``lax.scan`` keeps
``last_tok`` and the KV cache device-resident across the whole block and
returns a [K, n_slots] token block, ONE host sync per macro-step instead
of per token.  K is chosen adaptively each macro-step from scheduler
state: K=1 whenever the WFQ queues are non-empty, a slot is free, prefill
debt is outstanding, or the parity controller is near an escalation
boundary; only at batch-full steady state does K ramp toward K_max — so
admission latency and parity reactivity are preserved on exactly the
schedules where they matter.  The per-step control decisions (latency
draw, posterior update, parity level, erasure mask) still run on host,
one per fused step, BEFORE the block launches; the decode data plane is
bit-identical to K scalar steps because the scalar loop already decodes
every slot every step (inactive slots produce discarded tokens), so the
device trajectory does not depend on mid-block slot retirement.

Profiler spans: each step's host work is annotated with
``jax.profiler.TraceAnnotation``s, recorded only while a profiler trace
runs (``launch/serve.py --profile``): ``engine.step`` (arg ``k``) around
a scalar step or fused block, holding ``engine.admit`` (the admission
pass, with ``engine.prefill`` per request, args ``uid`` and ``tokens``,
and ``engine.splice``, arg ``slots``), ``engine.control`` (one per
decoded step), ``engine.launch`` (mask upload and dispatch),
``engine.sync`` (the host waiting for the token read) and
``engine.apply`` (bookkeeping).  On the device, the model's
``coded_head`` and ``kv_write`` named scopes reach the compiled
programs' ``op_name`` metadata; ``ServeEngine.op_scopes()`` maps each
compiled instruction to it.
"""
from __future__ import annotations

from collections import deque
from dataclasses import dataclass, field
from typing import TYPE_CHECKING, Any, Callable

import numpy as np

import jax
import jax.numpy as jnp

from repro.models.registry import Model
from repro.utils.hlo import op_names

_span = jax.profiler.TraceAnnotation

if TYPE_CHECKING:  # annotation-only: keeps the module import light
    from repro.core.adaptive import DeadlineAwareParity, ParityController
    from repro.serve.scheduler import TraceScheduler

__all__ = ["Request", "ServeEngine"]


@dataclass
class Request:
    uid: int
    prompt: np.ndarray               # [S] int32
    max_new_tokens: int = 16
    img_embed: np.ndarray | None = None
    out_tokens: list[int] = field(default_factory=list)
    deadline: float | None = None    # absolute SLO (scheduler-driven mode)
    sched_idx: int | None = None     # TraceScheduler request index
    finish_step: int | None = None   # engine step count at retirement

    @property
    def done(self) -> bool:
        return len(self.out_tokens) >= self.max_new_tokens


def _batch_axis(path) -> int | None:
    """Batch-dim index per cache leaf name (mirrors the cache layouts)."""
    name = path[-1].key if hasattr(path[-1], "key") else str(path[-1])
    if name == "pos":
        return 0
    if name in ("k", "v", "ck", "cv"):
        return -4
    if name == "ssm":
        return -4
    if name == "conv":
        return -3
    return None


class ServeEngine:
    def __init__(
        self,
        model: Model,
        params: Any,
        n_slots: int = 4,
        s_max: int = 256,
        mask_fn: Callable[[], np.ndarray] | None = None,
        eos_token: int | None = None,
        latency_fn: Callable[[], np.ndarray] | None = None,
        parity_controller: "ParityController | None" = None,
        parity_topup: int = 0,
        topup_patience: int = 4,
        encode_mode: str | None = None,
        mesh=None,
        head_axis: str = "model",
        head_kernel_mode: str | None = None,
        scheduler: "TraceScheduler | None" = None,
        parity_policy: "DeadlineAwareParity | None" = None,
        clock: Callable[[], float] | None = None,
        prefill_budget: int | None = None,
        macro_steps: int = 1,
    ):
        """``parity_topup`` allows the engine to RAISE the coded head's
        parity budget at runtime by up to that many blocks: when the
        ParityController's straggler posterior saturates the current budget
        for ``topup_patience`` consecutive steps, the head weight is
        re-encoded with one more parity block ON DEVICE through the tiled
        Pallas encode kernel (``kernels.ops.encode_blocks_device``,
        DESIGN.md §9) — the serving analogue of the executor's reserve
        top-up.  ``encode_mode`` is the kernel mode for those re-encodes
        (None follows the platform: the compiled kernel on a TPU, the jnp
        reference elsewhere).

        ``mesh`` shards the coded head over a real ``jax.sharding.Mesh``:
        whole code blocks per device along ``head_axis`` (whose size must
        divide the block count; one block per device makes erasure =
        dropping a device's output), decode via the mask-keyed
        DecoderCache — the single-device path is bit-identical on
        identical masks (DESIGN.md §10).  ``scheduler`` switches admission to a trace-driven
        ``serve.scheduler.TraceScheduler`` (open-loop arrivals, deadlines,
        admission control); its request payloads must be ``Request``
        objects.  ``parity_policy`` replaces the raw ParityController level
        with the deadline-aware rule (SLO slack from the scheduler; a
        ``TenantDeadlineParity`` policy is fed the PER-CLASS slack vector
        so each SLO class escalates at its own threshold); ``clock``
        supplies "now" (defaults to ``time.monotonic``; tests inject a
        fake model-time clock).

        ``prefill_budget`` disaggregates prefill from decode in the
        scheduler-driven refill: each step admits new requests only while
        the prompt tokens prefilled this step stay under the budget (the
        first admission always lands, so a long prompt cannot livelock).
        ``None`` keeps the PR 5 behaviour of refilling every free slot.

        ``head_kernel_mode`` selects the coded head's kernel
        implementation: ``'auto'`` consults the autotune dispatch table
        (analytical-model fallback for unseen shapes, DESIGN.md §11), an
        explicit mode pins one, None keeps the default cached path.  It is
        installed as a ``sharding.ctx.head_kernel_mode`` context inside the
        jitted step traces — same threading pattern as the head mesh.

        ``macro_steps`` is K_max for the fused macro-step decode
        (DESIGN.md §14): ``macro_step()`` may decode up to that many steps
        per jitted launch (one host sync per block) when the adaptive K
        policy says the control plane has nothing to do mid-block; 1 (the
        default) keeps every step scalar."""
        self.model, self.params = model, params
        self.n_slots, self.s_max = n_slots, s_max
        self.mask_fn = mask_fn
        self.latency_fn = latency_fn
        if parity_policy is not None:
            if parity_controller is None:
                parity_controller = parity_policy.controller
            elif parity_controller is not parity_policy.controller:
                raise ValueError(
                    "parity_policy wraps a different ParityController than "
                    "the one passed explicitly"
                )
        self.parity_controller = parity_controller
        self.parity_policy = parity_policy
        self.scheduler = scheduler
        if clock is None:
            import time

            clock = time.monotonic
        self._clock = clock
        self.parity_topup = parity_topup
        self.topup_patience = topup_patience
        self.prefill_budget = prefill_budget
        self.encode_mode = encode_mode
        self.head_kernel_mode = head_kernel_mode
        if macro_steps < 1:
            raise ValueError("macro_steps must be >= 1")
        self.macro_steps = int(macro_steps)
        self.parity_events: list[dict] = []
        self._saturated_steps = 0
        self._steps = 0
        # host-sync accounting (read by launch/serve.py, tests and
        # benchmarks/engine_bench.py)
        self.sync_count = 0         # device->host transfers on the hot path
        self.tokens_emitted = 0     # tokens appended to request outputs
        self.macro_blocks = 0       # fused blocks launched (K > 1)
        self.splice_rebuilds = 0    # full cache-pytree rebuilds (refill)
        self._pending_splice: list[tuple[int, Any]] = []
        # control decision computed for a step that has not decoded yet —
        # set when a mid-block parity raise truncates a fused block (the
        # post-raise step's control already ran; its decode is next)
        self._pending_ctrl: tuple | None = None
        self.eos_token = eos_token
        self.queue: deque[Request] = deque()
        self.slots: list[Request | None] = [None] * n_slots
        self.cache = model.init_cache(n_slots, s_max)
        self._last_prefill: dict | None = None  # op_scopes() lowers with it
        self._last_tok = jnp.zeros(n_slots, jnp.int32)  # device-resident
        self._active = np.zeros(n_slots, bool)
        if model.cfg.coded:
            from repro.models.transformer import _coded_blocks

            self._n_blocks = _coded_blocks(model.cfg)
        self._mesh = mesh
        self._head_axis = head_axis
        if mesh is not None:
            if not model.cfg.coded:
                raise ValueError("mesh-sharded head requires a coded model config")
            from repro.sharding.policy import (
                coded_head_sharding,
                validate_coded_head_mesh,
            )

            validate_coded_head_mesh(mesh, self._n_blocks, head_axis)
            # place every array once: the coded head with its block sharding
            # (so the per-step shard_map never reshards the weight), the
            # rest of the model, the cache and the token carry replicated
            # on the mesh (left on the default device they would be copied
            # to every other device at each step)
            from jax.sharding import NamedSharding, PartitionSpec

            rep = NamedSharding(mesh, PartitionSpec())
            head = self.params["lm_head_coded"]
            self.params = jax.device_put(
                {k: v for k, v in self.params.items() if k != "lm_head_coded"},
                rep,
            )
            self.params["lm_head_coded"] = jax.device_put(
                head, coded_head_sharding(mesh, head_axis)
            )
            self.cache = jax.device_put(self.cache, rep)
            self._last_tok = jax.device_put(self._last_tok, rep)
        self._bind_model(model)
        self.completed: list[Request] = []

    def _bind_model(self, model: Model) -> None:
        """(Re-)jit the decode/prefill steps for the given model config —
        called at init and after a parity-budget top-up re-encode."""
        from repro.sharding.ctx import coded_head_mesh, head_kernel_mode

        self.model = model
        s_max = self.s_max
        mesh, axis = self._mesh, self._head_axis
        kmode = self.head_kernel_mode

        def _decode_argmax(params, cache, last_tok, mask):
            with coded_head_mesh(mesh, axis), head_kernel_mode(kmode):
                logits, cache = model.decode_step(params, cache, last_tok, mask)
            return jnp.argmax(logits, axis=-1).astype(jnp.int32), cache

        def _prefill_argmax(params, batch):
            with coded_head_mesh(mesh, axis), head_kernel_mode(kmode):
                logits, cache1 = model.prefill(params, batch, s_max=s_max)
            return jnp.argmax(logits, axis=-1).astype(jnp.int32), cache1

        # the cache is donated: the step stores its new rows in place
        self._decode = jax.jit(_decode_argmax, donate_argnums=(1,))
        self._prefill1 = jax.jit(_prefill_argmax)
        # fused-block jit bucket cache, keyed by K.  Shape (n_slots, s_max)
        # and parity geometry are fixed per bind — a parity raise re-binds
        # and empties the dict — so the key IS the (K, shape, parity)
        # bucket (DESIGN.md §14)
        self._decode_block: dict[int, Any] = {}
        # per-bucket first-call tracking: the first launch of EVERY jitted
        # entry point after a (re-)bind is compile time, not step time.
        # The old single `_fresh_jit` flag only excused the first decode —
        # a parity raise followed by another re-jit path double-counted a
        # compile into the scheduler's EW step-time estimate
        self._compiled: set[tuple[str, int]] = set()
        # cached dummy scan xs per K: a fresh jnp.zeros(k) per block is a
        # device alloc + transfer on the hot path (the mask values are
        # never read by the unmasked head)
        self._zero_xs: dict[int, Any] = {}

    @property
    def _masked(self) -> bool:
        """Does a decode step take an erasure mask (else None)?"""
        return self.model.cfg.coded and (
            self.latency_fn is not None or self.mask_fn is not None
        )

    def op_scopes(self) -> dict[str, dict[str, str]]:
        """The ``op_name`` of every operation of the compiled scalar steps,
        keyed by program and instruction name (``reshape.65``, as a
        profiler's ``XLA Ops`` line names it): ``{"_decode_argmax": {...},
        "_prefill_argmax": {...}}``.  A ``jax.named_scope`` in the model
        (``coded_head``, ``kv_write``) shows as a path segment; "" where
        the compiler left no metadata.  Each step is lowered and compiled
        again with the arguments it last ran with (the prefill once one
        has run), so this is for operators and trace readers, never for
        the hot path."""
        mask = jnp.ones(self._n_blocks, jnp.float32) if self._masked else None
        out = {"_decode_argmax": self._decode.lower(
            self.params, self.cache, self._last_tok, mask)}
        if self._last_prefill is not None:
            out["_prefill_argmax"] = self._prefill1.lower(
                self.params, self._last_prefill)
        return {k: op_names(v.compile().as_text()) for k, v in out.items()}

    # ------------------------------------------------------------------
    def submit(self, req: Request) -> None:
        self.queue.append(req)

    def _insert_slot(self, slot: int, req: Request) -> None:
        """Prefill one request (B=1) and stage its cache for the batch.

        The actual splice into the batch cache is DEFERRED: admissions in
        one refill pass coalesce into a single pytree rebuild
        (``_flush_splices``) instead of one full-tree ``.at[].set`` chain
        per request — the decode cache has dozens of leaves, and a burst
        of admissions used to pay the whole-tree rebuild once each."""
        batch = {"tokens": jnp.asarray(req.prompt[None])}
        if req.img_embed is not None:
            batch["img_embed"] = jnp.asarray(req.img_embed[None])
        if self.model.cfg.family == "encdec":
            batch["frames"] = jnp.asarray(
                np.zeros((1, len(req.prompt), self.model.cfg.d_model), np.float32)
            )
        self._last_prefill = batch
        with _span("engine.prefill", uid=req.uid, tokens=len(req.prompt)):
            tok1, cache1 = self._prefill1(self.params, batch)
            self._pending_splice.append((slot, cache1))
            self._last_tok = self._last_tok.at[slot].set(tok1[0])  # device-side
            req.out_tokens.append(int(np.asarray(tok1)[0]))
        self.sync_count += 1
        self.tokens_emitted += 1
        self.slots[slot] = req
        self._active[slot] = True

    def _flush_splices(self) -> None:
        """Apply every staged admission in ONE cache-pytree rebuild.

        A slot admitted twice in one pass (a request that finished at
        prefill freed it for a later admission) keeps the LAST cache —
        same final state as sequential splices; ``.at`` with duplicate
        indices is unspecified, so the dedup is required, not cosmetic."""
        if not self._pending_splice:
            return
        by_slot: dict[int, Any] = {}
        for slot, cache1 in self._pending_splice:
            by_slot[slot] = cache1
        self._pending_splice = []
        slot_list = sorted(by_slot)
        ones = [by_slot[s] for s in slot_list]
        slots_idx = jnp.asarray(slot_list)

        def splice(path, full, *cs):
            ax = _batch_axis(path)
            if ax is None:
                return full
            ax = ax % full.ndim
            idx: list = [slice(None)] * full.ndim
            idx[ax] = slots_idx
            one_ax = ax if ax < cs[0].ndim else cs[0].ndim - 1
            tgt = full.shape[:ax] + full.shape[ax + 1:]
            srcs = []
            for one in cs:
                src = jnp.take(one, 0, axis=one_ax)
                # pad the sequence axis of k/v to the batch cache capacity
                if src.shape != tgt:
                    pads = [(0, t - s) for s, t in zip(src.shape, tgt)]
                    src = jnp.pad(src, pads)
                srcs.append(src.astype(full.dtype))
            return full.at[tuple(idx)].set(jnp.stack(srcs, axis=ax))

        with _span("engine.splice", slots=len(slot_list)):
            self.cache = jax.tree_util.tree_map_with_path(splice, self.cache, *ones)
        self.splice_rebuilds += 1

    def _finish_slot(self, slot: int, req: Request, now: float | None) -> None:
        """Retire a request and free its slot — THE one completion path
        (prefill-completed, EOS, and budget-exhausted all land here, so
        the slot is reusable the same step and can never double-retire)."""
        if self.scheduler is not None and req.sched_idx is not None:
            self.scheduler.on_finish(req.sched_idx, now)
        req.finish_step = self._steps
        self.completed.append(req)
        self._active[slot] = False
        self.slots[slot] = None

    def _prefill_done(self, req: Request) -> bool:
        """Did the prefill's own first token already end this request?
        (1-token budget, or EOS as the very first output.)  Generalizes
        the PR 5 one-token fix: ANY way a request can end at prefill must
        free the slot before the next decode step, or that step would emit
        past the budget / past EOS (regression-tested in
        tests/test_serve_batch.py)."""
        hit_eos = (
            self.eos_token is not None
            and req.out_tokens
            and req.out_tokens[-1] == self.eos_token
        )
        return req.done or hit_eos

    def _refill(self, now: float | None = None) -> None:
        """One admission pass; all admitted caches land in a single
        batched splice (one tree rebuild per pass, not per request)."""
        with _span("engine.admit"):
            try:
                self._admit_refill(now)
            finally:
                self._flush_splices()

    def _admit_refill(self, now: float | None = None) -> None:
        if self.scheduler is not None:
            prompt_spent = 0
            while True:
                free = int(self.n_slots - self._active.sum())
                if free <= 0:
                    return
                admitted = self.scheduler.admit(now, 1)
                if not admitted:
                    return
                sreq = admitted[0]
                req = sreq.payload
                if not isinstance(req, Request):
                    raise TypeError(
                        "scheduler-driven engine needs Request payloads on "
                        "the TraceScheduler trace"
                    )
                if req.max_new_tokens != sreq.n_tokens:
                    raise ValueError(
                        f"request {req.uid}: payload token budget "
                        f"{req.max_new_tokens} != trace n_tokens "
                        f"{sreq.n_tokens} — the engine and scheduler would "
                        f"disagree on completion"
                    )
                req.sched_idx = sreq.idx
                req.deadline = sreq.deadline
                slot = int(np.flatnonzero(~self._active)[0])
                self._insert_slot(slot, req)
                prompt_spent += len(req.prompt)
                # the prefill already emitted this request's first token —
                # which can COMPLETE the request (1-token budget, or EOS as
                # the first output): free its slot now, or the next decode
                # step would emit past its budget.  The token is stamped
                # with a FRESH clock read: the prefill (and its first-call
                # jit compile) took real wall time, and a pre-prefill stamp
                # would count deadline-expired requests as met
                t_tok = self._clock()
                done = self.scheduler.on_token(sreq.idx, t_tok)
                if done or self._prefill_done(req):
                    self._finish_slot(slot, req, t_tok)
                # prefill/decode disaggregation: stop admitting once this
                # step's prompt-token budget is spent (the admission above
                # always lands, so long prompts make progress)
                if self.prefill_budget is not None and (
                    prompt_spent >= self.prefill_budget
                ):
                    return
        else:
            for s in range(self.n_slots):
                if not self._active[s] and self.queue:
                    req = self.queue.popleft()
                    self._insert_slot(s, req)
                    # same seam as the scheduler path: a request whose
                    # prefill token already satisfied it must not see a
                    # decode step (max_new_tokens=1 double-emitted here
                    # before the fix)
                    if self._prefill_done(req):
                        self._finish_slot(s, req, None)

    def _raise_parity(self) -> None:
        """Re-encode the coded head with ONE more parity block, on device.

        The block-MDS head has a fixed block count (one per shard), so a
        bigger parity budget means a (n_data-1, n_parity+1) re-split — a
        full re-encode of the head weight, which is exactly the job of the
        tiled Pallas encode kernel: weights in, coded blocks out, no host
        round-trip.  The decode/prefill steps re-jit once per raise."""
        import dataclasses

        from repro.kernels.ops import encode_blocks_device, platform_mode
        from repro.models.registry import build_model

        cfg = self.model.cfg
        new_parity = cfg.coded_parity + 1
        head = (
            self.params["lm_head"]
            if "lm_head" in self.params
            else self.params["embed"].T
        )
        pdt = self.params["lm_head_coded"].dtype
        mode = self.encode_mode or platform_mode()
        coded = encode_blocks_device(
            head.T.astype(jnp.float32),
            self._n_blocks - new_parity,
            new_parity,
            mode=mode,
        )
        # shallow-copy so the caller's params dict (possibly shared with
        # other engines) keeps its original-geometry coded head
        self.params = dict(self.params)
        coded = coded.astype(pdt)
        if self._mesh is not None:
            from repro.sharding.policy import coded_head_sharding

            coded = jax.device_put(
                coded, coded_head_sharding(self._mesh, self._head_axis)
            )
        self.params["lm_head_coded"] = coded
        self._bind_model(build_model(dataclasses.replace(cfg, coded_parity=new_parity)))
        self.parity_topup -= 1
        self._saturated_steps = 0
        self.parity_events.append({
            "step": self._steps,
            "n_parity": new_parity,
            "encode_mode": mode,
        })

    # ------------------------------------------------------------------
    def _control_step(self, now: float | None) -> np.ndarray | None:
        """One step's host control plane: observe latencies through the
        parity policy/controller, run saturation top-up, convert slack to
        a parity level, and commit this step's erasure mask (None when the
        head is uncoded/unmasked).  Mutates controller state exactly as
        the scalar loop always has — the fused path calls this once per
        fused step BEFORE launching the block, so posterior trajectories
        match the scalar loop bit for bit."""
        if self.model.cfg.coded and self.latency_fn is not None:
            # first decodable subset: keep the n_data earliest shards this
            # step, drop the laggards — the mask-keyed DecoderCache decodes
            # any such subset without waiting for the slowest n_parity
            from repro.core.decoding import first_decodable_mask

            lat = np.asarray(self.latency_fn(), np.float64)
            if self.mask_fn is not None:  # dead shards never count as fast
                lat = np.where(np.asarray(self.mask_fn()) > 0.5, lat, np.inf)
            n_blocks = self._n_blocks
            n_par = self.model.cfg.coded_parity
            if self.parity_controller is not None:
                # adaptive parity: drop only the shards the recent straggler
                # posterior believes are laggards (<= the code's budget).
                # Observation goes THROUGH the deadline policy when one is
                # wired in — its calm/onset/spike economics feed on the
                # same stream (a controller-only observe would freeze the
                # policy at its pessimistic priors, i.e. fixed-parity).
                if self.parity_policy is not None:
                    self.parity_policy.observe(lat)
                else:
                    self.parity_controller.observe(lat)
                believed = int((self.parity_controller.posterior > 0.5).sum())
                if believed > n_par and self.parity_topup > 0:
                    # more persistent stragglers than the budget covers:
                    # after `topup_patience` consecutive saturated steps,
                    # encode one more parity block (on device) and re-split
                    self._saturated_steps += 1
                    if self._saturated_steps >= self.topup_patience:
                        self._raise_parity()
                        n_par = self.model.cfg.coded_parity
                else:
                    self._saturated_steps = 0
                if self.parity_policy is not None:
                    # deadline-aware level: SLO slack (in estimated steps,
                    # +inf without a scheduler) escalates toward the full
                    # budget; ample slack degrades to the posterior count.
                    # A per-tenant policy gets the per-class slack vector —
                    # each SLO class converts its own slack at its own
                    # escalation threshold and the step runs at the max
                    from repro.core.adaptive import TenantDeadlineParity

                    if self.scheduler is None:
                        slack: Any = np.inf
                    elif isinstance(self.parity_policy, TenantDeadlineParity):
                        slack = self.scheduler.class_slack_steps(now)
                    else:
                        slack = self.scheduler.min_slack_steps(now)
                    n_par = self.parity_policy.level(n_par, slack)
                else:
                    n_par = self.parity_controller.parity_level(n_par)
            return np.asarray(
                first_decodable_mask(lat, n_blocks - n_par, n_par), np.float32
            )
        if self.mask_fn is not None and self.model.cfg.coded:
            return np.asarray(self.mask_fn(), np.float32)
        return None

    def _apply_step(self, toks: np.ndarray, t_done: float | None) -> None:
        """Post-decode bookkeeping for one step's [n_slots] token row:
        output accumulation, EOS, scheduler completion, slot retirement."""
        for s in range(self.n_slots):
            if not self._active[s]:
                continue
            req = self.slots[s]
            tok = int(toks[s])
            req.out_tokens.append(tok)
            self.tokens_emitted += 1
            hit_eos = self.eos_token is not None and tok == self.eos_token
            done_sched = False
            if self.scheduler is not None and req.sched_idx is not None:
                done_sched = self.scheduler.on_token(req.sched_idx, t_done)
            if req.done or hit_eos or done_sched:
                # EOS can land before the token budget: _finish_slot force-
                # completes on the scheduler and frees the slot this step
                self._finish_slot(s, req, t_done)

    def step(self) -> int:
        """One batched decode step; returns number of active sequences."""
        with _span("engine.step", k=1):
            now = self._clock() if self.scheduler is not None else None
            self._refill(now)
            if not self._active.any():
                return 0
            self._steps += 1
            if self._pending_ctrl is not None:
                # a truncated fused block already ran this step's control
                m = self._pending_ctrl[0]
                self._pending_ctrl = None
            else:
                with _span("engine.control"):
                    m = self._control_step(now)
            with _span("engine.launch"):
                mask = None if m is None else jnp.asarray(m, jnp.float32)
                # step-time measurement starts HERE: _refill's prefills (and
                # their jit compiles) are admission work, not decode-step time
                t_decode0 = self._clock() if self.scheduler is not None else None
                toks_dev, self.cache = self._decode(
                    self.params, self.cache, self._last_tok, mask
                )
            self._last_tok = toks_dev       # feeds next step, never leaves device
            with _span("engine.sync"):
                toks = np.asarray(toks_dev)  # the ONE host transfer per step
            self.sync_count += 1
            t_done = None
            if self.scheduler is not None:
                t_done = self._clock()
                if ("decode", 1) in self._compiled:
                    self.scheduler.observe_step(t_done - t_decode0)
                else:
                    # first call of this jit bucket since the (re-)bind: the
                    # duration is compile time, not a step time — feeding it
                    # would poison the EW estimate and make admission reject
                    # feasible arrivals
                    self._compiled.add(("decode", 1))
            elif ("decode", 1) not in self._compiled:
                self._compiled.add(("decode", 1))
            with _span("engine.apply"):
                self._apply_step(toks, t_done)
            return int(self._active.sum())

    # ------------------------------------------------------------------
    # fused macro-step decode (DESIGN.md §14)
    # ------------------------------------------------------------------
    def _ctrl_snapshot(self) -> tuple:
        """Controller/policy state needed to roll back control decisions
        taken for fused steps that end up never decoding (the batch
        drained mid-block)."""
        ctrl, pol = self.parity_controller, self.parity_policy
        return (
            None if ctrl is None else ctrl.posterior.copy(),
            None if pol is None else (
                pol._onset_rate, pol._spike, pol._calm_steps
            ),
            self._saturated_steps,
        )

    def _ctrl_restore(self, snap: tuple) -> None:
        post, pol_state, sat = snap
        if post is not None:
            self.parity_controller.posterior = post
        if pol_state is not None:
            pol = self.parity_policy
            pol._onset_rate, pol._spike, pol._calm_steps = pol_state
        self._saturated_steps = sat

    def _choose_k(self) -> int:
        """Fused block length for the NEXT macro-step, from control-plane
        state: K=1 whenever any per-step control decision could differ
        mid-block — queued work, a free slot, prefill debt, an imminent
        arrival, scarce deadline slack, or a parity controller near its
        escalation boundary.  Only a full batch at steady state ramps
        toward ``macro_steps``; K is quantized down to a power of two so
        the jit bucket cache stays small."""
        if self.macro_steps <= 1 or not self._active.any():
            return 1
        if self.queue or not self._active.all():
            return 1  # admission work possible: stay reactive
        k = self.macro_steps
        # cap at the longest remaining token budget (after that the whole
        # batch has drained; EOS can still empty it earlier — the replay
        # loop rolls back the over-provisioned control steps)
        rem = max(
            req.max_new_tokens - len(req.out_tokens)
            for s, req in enumerate(self.slots)
            if self._active[s]
        )
        k = min(k, max(rem, 1))
        sched = self.scheduler
        if sched is not None:
            now = self._clock()
            if sched.pending(now) > 0 or sched.has_prefill_debt:
                return 1
            est = max(sched.est_step_time, 1e-12)
            nxt = sched.next_arrival()
            if nxt is not None:
                # never decode past the next arrival's admission step
                k = min(k, max(1, int((nxt - now) / est)))
            if self.parity_policy is not None:
                # never fuse past the point slack could force escalation
                esc = max(
                    getattr(self.parity_policy, "class_escalate",
                            (self.parity_policy.escalate_steps,))
                )
                slack = sched.min_slack_steps(now)
                if np.isfinite(slack):
                    k = min(k, max(1, int(slack - esc)))
        if self.parity_controller is not None and self.parity_topup > 0:
            believed = int((self.parity_controller.posterior > 0.5).sum())
            if self._saturated_steps > 0 or believed >= self.model.cfg.coded_parity:
                return 1  # a top-up raise may be steps away: stay scalar
        p = 1
        while p * 2 <= k:
            p *= 2
        return p

    def _block_fn(self, k: int):
        """The K-bucket jitted block: ``lax.scan`` over K decode steps,
        device-resident carry (last_tok, cache), [K, n_slots] token block
        out.  Buckets are cached per bind — shape and parity geometry are
        fixed between binds, so K alone keys the (K, shape, parity)
        bucket."""
        fn = self._decode_block.get(k)
        if fn is not None:
            return fn
        from repro.sharding.ctx import (
            coded_head_mesh,
            head_kernel_mode,
            macro_step_k,
        )

        model = self.model
        mesh, axis = self._mesh, self._head_axis
        kmode = self.head_kernel_mode
        masked = self._masked

        def _decode_block(params, cache, last_tok, masks):
            def body(carry, m):
                lt, c = carry
                with coded_head_mesh(mesh, axis), head_kernel_mode(kmode), \
                        macro_step_k(k):
                    logits, c = model.decode_step(
                        params, c, lt, m if masked else None
                    )
                tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                return (tok, c), tok

            (lt, cache), toks = jax.lax.scan(body, (last_tok, cache), masks)
            return toks, lt, cache

        fn = jax.jit(_decode_block, donate_argnums=(1,))
        self._decode_block[k] = fn
        return fn

    def _fused_block(self, k: int) -> int:
        """Decode ``k`` steps in one jitted launch with ONE host sync.

        Control runs first, k times on host (masks, posteriors, top-up
        checks — scalar-exact mutation order); then the block launches and
        the [k, n_slots] token rows replay through the scalar
        bookkeeping.  Two truncation paths keep scalar equivalence:

          * a mid-block parity RAISE re-binds the model, so the pre-raise
            steps replay through the OLD jitted step scalar-wise and the
            post-raise control result is stashed for the next ``step()``;
          * the batch DRAINING mid-block (EOS) stops the replay early and
            rolls controller state back to the last executed step — the
            scalar loop would never have run those trailing control steps.
            (``latency_fn``-internal state — health monitors, RNG — stays
            advanced; an all-slots drain in the same block as a raise
            additionally cannot un-encode.  Both are outside the fused
            gate's steady-state envelope and documented in DESIGN.md §14.)
        """
        with _span("engine.step", k=k):
            now = self._clock() if self.scheduler is not None else None
            self._refill(now)  # the K gate makes this a no-op; seam kept
            if not self._active.any():
                return 0
            s0 = self._steps
            n_events = len(self.parity_events)
            old_decode, old_params = self._decode, self.params
            comp_before = self._compiled
            snaps: list[tuple] = []
            masks: list[np.ndarray | None] = []
            raised = False
            for t in range(k):
                snaps.append(self._ctrl_snapshot())
                self._steps = s0 + t + 1  # raise events record scalar-exact steps
                with _span("engine.control"):
                    m = self._control_step(now)
                if len(self.parity_events) > n_events:
                    raised = True
                    self._pending_ctrl = (m,)  # the post-raise step's control
                    break
                masks.append(m)
            self._steps = s0
            k_exec = len(masks)
            if raised and k_exec == 0:
                return self.step()  # consumes the pending control immediately
            if raised:
                # degrade: replay the pre-raise steps through the OLD jitted
                # scalar step (the raise re-bound self._decode to the new
                # geometry; these steps belong to the old one)
                executed = 0
                for t in range(k_exec):
                    self._steps += 1
                    m = masks[t]
                    mask = None if m is None else jnp.asarray(m, jnp.float32)
                    t0 = self._clock() if self.scheduler is not None else None
                    toks_dev, self.cache = old_decode(
                        old_params, self.cache, self._last_tok, mask
                    )
                    self._last_tok = toks_dev
                    toks = np.asarray(toks_dev)
                    self.sync_count += 1
                    t_done = None
                    if self.scheduler is not None:
                        t_done = self._clock()
                        if ("decode", 1) in comp_before:
                            self.scheduler.observe_step(t_done - t0)
                        else:
                            comp_before.add(("decode", 1))
                    self._apply_step(toks, t_done)
                    executed += 1
                    if not self._active.any():
                        break
                if not self._active.any():
                    # the batch drained before the post-raise step ran: its
                    # stashed control must not leak onto a future step, and
                    # the scalar loop would have stopped at `executed`
                    self._pending_ctrl = None
                    self._ctrl_restore(snaps[executed])
                return int(self._active.sum())
            blk = self._block_fn(k)
            fresh = ("decode", k) not in self._compiled
            self._compiled.add(("decode", k))
            with _span("engine.launch"):
                if masks[0] is None:
                    mstack = self._zero_xs.get(k)  # dummy scan xs, unmasked head
                    if mstack is None:
                        mstack = self._zero_xs[k] = jnp.zeros(k)
                else:
                    mstack = jnp.asarray(np.stack(masks), jnp.float32)
                t0 = self._clock() if self.scheduler is not None else None
                toks_blk, self._last_tok, self.cache = blk(
                    self.params, self.cache, self._last_tok, mstack
                )
            with _span("engine.sync"):
                toks = np.asarray(toks_blk)  # THE one host transfer for the block
            self.sync_count += 1
            self.macro_blocks += 1
            t_done = None
            dt = 0.0
            if self.scheduler is not None:
                t_done = self._clock()
                dt = (t_done - t0) / k  # per-step share of the block time
            executed = 0
            with _span("engine.apply"):
                for t in range(k):
                    self._steps += 1
                    if self.scheduler is not None and not fresh and dt > 0:
                        # K equal observes of the block mean: same total EW mass
                        # as the scalar loop's K per-step observes
                        self.scheduler.observe_step(dt)
                    self._apply_step(toks[t], t_done)
                    executed += 1
                    if not self._active.any():
                        break
            if executed < k:
                # EOS drained the batch early: the scalar loop would have
                # stopped here — roll back the trailing control decisions
                self._ctrl_restore(snaps[executed])
            return int(self._active.sum())

    def macro_step(self) -> int:
        """One macro-step: a fused K-step block at batch-full steady
        state, a scalar ``step()`` whenever the control plane needs per-
        step reactivity.  Drop-in replacement for ``step()`` in drive
        loops; with ``macro_steps=1`` it IS ``step()``."""
        if self.macro_steps <= 1 or self._pending_ctrl is not None:
            return self.step()
        k = self._choose_k()
        if k <= 1:
            return self.step()
        return self._fused_block(k)

    def run(self, max_steps: int = 10_000) -> list[Request]:
        """Drain the queue (or, with a scheduler, the trace — the caller's
        clock must advance past arrivals; see launch.serve for the
        wall-clock drive loop).  Returns completed requests.  Iterates
        ``macro_step()``: scalar per-step behaviour unless ``macro_steps``
        opted into fused blocks."""
        for _ in range(max_steps):
            busy = self.macro_step()
            if self.scheduler is not None:
                if self.scheduler.finished and busy == 0:
                    break
            elif busy == 0 and not self.queue:
                break
        return self.completed
