"""Serving engine: the paper's two-stage workload as a production loop.

summarization stage -> `prefill` (one jit'd GEMM-heavy program)
generation stage    -> `decode_step` (one jit'd GEMV-heavy program,
                       executed once per output token — the memory-bound
                       loop SAL-PIM accelerates)

Two drivers:
  * `generate`      — whole-batch generation, decode loop via lax.scan
                      inside one jit (zero per-token dispatch overhead —
                      the 'end-to-end in PIM, no host switching' analogue);
  * `ServingEngine` — slot-based continuous batching: fixed B decode
                      slots; finished sequences release their slot and
                      queued requests join at the next step boundary,
                      under the same compiled decode_step.
"""
from __future__ import annotations

import collections
import dataclasses
import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.salpim import SalPimEngine
from repro.distributed import api as dist_api
from repro.kernels import paged_attention as paged_k
from repro.kernels import paged_prefill as paged_pf_k
from repro.models import api as model_api
from repro.serving import kvcache as kv
from repro.models.config import ModelConfig
from repro.serving.config import EngineConfig, GenConfig
from repro.serving.costmodel import CostModel, StepShape
from repro.serving.sampling import sample
from repro.serving.scheduler import FifoScheduler, SwappedRequest
from repro.serving.speculative import greedy_accept, make_drafter
from repro.serving.telemetry import NULL_TELEMETRY, Telemetry

Array = jax.Array

__all__ = ["EngineConfig", "GenConfig", "Request", "ServingEngine",
           "generate"]


class _Counters:
    """Scheduler-action counters (preemptions / swap-outs / swap-ins),
    incremented in exactly one spot each *together with* the matching
    telemetry `sched.*` counters — host-side stats() and the telemetry
    snapshot cannot drift, whichever engine path (single-device or
    mesh-sharded) triggered the action."""

    def __init__(self, telemetry: Telemetry):
        self._tel = telemetry
        self.preemptions = 0
        self.swap_outs = 0
        self.swap_ins = 0

    def preempt(self) -> None:
        self.preemptions += 1
        self._tel.count("sched.preempt")

    def swap_out(self, pages: int) -> None:
        self.swap_outs += 1
        self._tel.count("sched.swap_out")
        self._tel.count("sched.swap_out_pages", pages)

    def swap_in(self, pages: int) -> None:
        self.swap_ins += 1
        self._tel.count("sched.swap_in")
        self._tel.count("sched.swap_in_pages", pages)

    def readmit(self) -> None:
        # Aborted mid-prefill entries re-admit without a blob: no pages
        # move, so only the telemetry event fires.
        self._tel.count("sched.readmit")


def _under_mesh(mesh, fn):
    """Call `fn` inside `distributed.api.use_mesh(mesh)` so its trace
    (first call) sees the mesh via current_mesh() and compiles the
    shard_map paged-attention path."""
    def call(*args):
        with dist_api.use_mesh(mesh):
            return fn(*args)
    return call


def generate(params: dict, prompts: Array, model_cfg: ModelConfig,
             engine: SalPimEngine, gen: GenConfig,
             *, extra_inputs: Optional[dict] = None,
             key: Optional[Array] = None) -> tuple[Array, dict]:
    """prompts (B, S) -> generated tokens (B, max_new_tokens).

    One jit for prefill, one jit'd scan for the whole decode loop.
    """
    B, S = prompts.shape
    key = key if key is not None else jax.random.PRNGKey(0)
    max_len = S + gen.max_new_tokens + 1
    batch = {"tokens": prompts, **(extra_inputs or {})}

    t0 = time.perf_counter()
    prefill_fn = jax.jit(
        lambda p, b: model_api.prefill(p, b, model_cfg, engine,
                                       max_len=max_len))
    logits, cache = prefill_fn(params, batch)
    jax.block_until_ready(logits)
    t_prefill = time.perf_counter() - t0

    def scan_body(carry, k_i):
        logits, cache, done = carry
        tok = sample(logits, k_i, temperature=gen.temperature,
                     top_k=gen.top_k)
        tok = jnp.where(done, gen.eos_id, tok)
        new_logits, new_cache = model_api.decode_step(
            params, tok, cache, model_cfg, engine)
        new_done = done | (tok == gen.eos_id) if gen.stop_on_eos else done
        return (new_logits, new_cache, new_done), tok

    t0 = time.perf_counter()
    keys = jax.random.split(key, gen.max_new_tokens)
    decode_fn = jax.jit(lambda c, ks: jax.lax.scan(scan_body, c, ks))
    done0 = jnp.zeros((B,), bool)
    (_, _, _), toks = decode_fn((logits, cache, done0), keys)
    toks = jnp.moveaxis(toks, 0, 1)  # (B, T)
    jax.block_until_ready(toks)
    t_decode = time.perf_counter() - t0

    # Honest token accounting: a sequence that hits EOS at step k emitted
    # k+1 real tokens; the scan still pads to max_new_tokens with EOS,
    # but those padding positions are not generated work.
    toks_host = np.asarray(toks)
    if gen.stop_on_eos:
        is_eos = toks_host == gen.eos_id
        n_per_seq = np.where(is_eos.any(axis=1),
                             is_eos.argmax(axis=1) + 1,
                             toks_host.shape[1])
    else:
        n_per_seq = np.full((B,), gen.max_new_tokens)
    n_tokens = int(n_per_seq.sum())
    stats = {
        "prefill_sec": t_prefill,
        "decode_sec": t_decode,
        "sec_per_token": t_decode * B / max(n_tokens, 1),
        "tokens": n_tokens,
        "tokens_budget": int(B * gen.max_new_tokens),
    }
    return toks, stats


@dataclasses.dataclass
class Request:
    uid: int
    prompt: np.ndarray
    max_new_tokens: int
    generated: list = dataclasses.field(default_factory=list)
    done: bool = False
    # Prompt tokens whose KV is already resident in the slot's pages
    # (continuous batching: a request decodes only once the cursor has
    # walked the whole prompt, one chunk per engine step).
    prefill_cursor: int = 0
    # Tokens covered by prefix-cache pages mapped at admission.
    # Distinguishes pages this request *borrowed* (COW-fork before
    # any write) from fresh pages it registered itself — the donor keeps
    # writing its registered pages even after a sharer raises their
    # refcount, since that write *is* the content sharers mapped.
    shared_prompt_tokens: int = 0
    # Speculative decoding stats: drafts the drafter proposed for this
    # request and how many the target's verify pass accepted.
    proposed: int = 0
    accepted: int = 0
    # Scheduling class (lower = more urgent; FIFO ignores it) and how
    # many times this request was preempted off its slot.
    priority: int = 0
    preemptions: int = 0

    @property
    def prefilling(self) -> bool:
        return self.prefill_cursor < len(self.prompt)


class ServingEngine:
    """Slot-based continuous batching over a fixed decode batch width.

    Slots share a page pool (kvcache.py). Admission is gated on the
    allocator's watermark: a request is admitted only when its
    worst-case page count can be reserved, so decode never runs out of
    pages mid-sequence. Pages are physically allocated at decode-step
    boundaries and freed the moment a request completes, so mixed
    prompt/output lengths never each pin a full `max_len` of KV.

    Prefill is *chunked*: prompts are written directly into pool
    pages, `prefill_chunk_tokens` tokens per engine step (None = the
    whole prompt in one chunk), with earlier chunks' KV read back
    through the block table. Admission only reserves pages; each step
    then runs at most one prompt chunk *alongside* the regular decode
    batch, so a long prompt does not stall resident decodes (continuous
    batching). A mid-prefill slot keeps device length 0 and an
    all-trash block-table row, so the shared decode program cannot
    touch its pages; the slot is activated (row + length + first
    logits) when the cursor reaches the end of the prompt.

    Prompt prefixes are shared (`prefix_sharing`, on by default):
    admission walks the allocator's content-addressed prefix cache,
    maps the longest cached run of full pages into the new slot, and
    the chunked prefill simply starts at the shared offset. Shared
    pages are copy-on-write: a KV write that would land in a page with
    refcount > 1 first forks it into a private physical page. Greedy
    outputs are bit-identical with sharing on or off and at any chunk
    size — both only remove redundant work and pool pressure.

    `kv_cache_dtype="int8"` stores the page pools as int8 with
    per-(token, head) f32 scale rows: every KV write — chunk prefill
    and decode append — amax-quantizes at write time and the paged
    kernels dequantize in VMEM, halving the HBM bytes a decode step
    streams. With `num_pages=None` the pool keeps the *byte* budget of
    the fp pool, so it holds ~2x the pages (double resident capacity at
    fixed HBM). COW forks copy scale rows with their pages. Outputs
    match the fp engine's greedy outputs up to quantization noise
    (~1/127 per K/V vector) — exact on the repo's test prompts. int8
    scale rows default to f32; `kv_scale_dtype="bfloat16"` stores them
    in bf16 — (Dh + 2) instead of (Dh + 4) bytes per vector.

    `kv_cache_dtype="int4"` packs two KV values per byte ((Dh/2 + 2)
    bytes per vector — half of int8 again; requires
    `kv_scale_dtype="bfloat16"`). Same write-time quantization and
    in-kernel unpack+dequant contract; with `num_pages=None` the fp
    byte budget holds ~4-8x the pages. Quantization noise is ~1/7 per
    vector — still greedy-exact on the repo's smoke workloads, but
    validate on your own.

    `kv_splits=K` turns long-context decode attention into the KV-split
    (flash-decode) form: the block-table walk is split into K
    online-softmax partials merged by `merge_partial_softmax_stacked`.
    Engaged only above `KV_SPLIT_MIN_CONTEXT` resident tokens; outputs
    match the single walk to float tolerance (~1e-6), not bit-exactly.

    `speculative=SpecConfig(...)` (greedy only) turns decode steps into
    draft-verify rounds (serving/speculative.py): a drafter proposes k
    tokens, one verify pass scores all of them against the pool, the
    accepted prefix commits and the rejected tail rolls back in-pool.
    Greedy outputs stay bit-identical with speculation on or off;
    mid-prefill slots never speculate (they are not in the decode batch
    until their prompt cursor finishes).

    `scheduler=` (serving/scheduler.py) selects the admission /
    prefill-ordering / preemption policy. The default `FifoScheduler`
    is strict FIFO under watermark admission, no preemption.
    `SloScheduler` adds priority classes (`submit(priority=...)`),
    optimistic (non-worst-case) admission, and preempt-and-swap over
    the host tier (`kvcache.HostSwapTier`) when the pool runs dry —
    swapped-then-restored slots continue bit-identically, and any
    schedule that never preempts keeps greedy outputs bit-identical to
    FIFO.

    `telemetry=Telemetry(enabled=True)` (serving/telemetry.py) attaches
    the observability layer: per-step phase records (admit / chunk
    prefill / draft / verify / decode), pool occupancy + watermark
    gauges, per-request lifecycle traces (submit -> admit -> chunks ->
    tokens -> finish), and allocator counters (prefix-cache hits, COW
    forks, admission rejections). The default is a no-op: nothing is
    recorded, no host sync is added, and serving outputs are
    bit-identical with telemetry on or off — instrumentation lives at
    step boundaries only, never inside the jitted programs.

    `mesh=jax.sharding.Mesh(devices, ("model",))` serves the page pools
    sharded across devices: payload and scale pools shard their
    KV-head axis over the mesh axis behind the logical "model" name,
    weights/block tables/lengths replicate, and the decode/prefill
    kernels run inside `shard_map` on per-shard head slices with an
    exact concatenation merge (collectives.gather_heads) — greedy
    outputs stay bit-identical to the single-device engine while each
    device holds 1/tp of the pool bytes. Admission, scheduling, COW
    forks, rewind and preempt-swap stay host-side and global, so every
    feature works unchanged on a mesh.

    Construction: `ServingEngine(params, cfg, engine,
    EngineConfig(slots=4, max_len=64))` (serving/config.py).
    """

    def __init__(self, params: dict, model_cfg: ModelConfig,
                 engine: SalPimEngine, config: EngineConfig):
        # One place for every feature-interaction rule (spec is
        # greedy-only, mesh divides KV heads, int4 needs bf16 scales...)
        config.validate(model_cfg)
        self.config = config
        slots, max_len, gen = config.slots, config.max_len, config.gen
        self.params = params
        self.cfg = model_cfg
        self._layer_windows = collections.Counter(
            model_cfg.window_for_layer(i) for i in range(model_cfg.n_layers))
        # The KV-split autotune knob rides the SalPim engine config so
        # it reaches paged_decode_attention with zero model-layer
        # signature changes (the engine closes over it inside jit).
        if config.kv_splits is not None and config.kv_splits > 1:
            engine = dataclasses.replace(
                engine, config=dataclasses.replace(
                    engine.config, kv_splits=config.kv_splits))
        self.engine = engine
        self.slots = slots
        self.max_len = max_len
        self.gen = gen
        self.telemetry = (config.telemetry if config.telemetry is not None
                          else NULL_TELEMETRY)
        self.scheduler = (config.scheduler if config.scheduler is not None
                          else FifoScheduler())
        self.mesh = config.mesh
        self.queue: list[Request] = []
        self.active: list[Optional[Request]] = [None] * slots
        self.finished: list[Request] = []
        # Preempted requests parked off-device (scheduler.SwappedRequest)
        # and the host-RAM tier holding their exact KV payloads.
        self.swapped: list[SwappedRequest] = []
        self.swap_tier = kv.HostSwapTier()
        self._counters = _Counters(self.telemetry)
        self.last_logits = jnp.zeros((slots, model_cfg.vocab), jnp.float32)
        self._uid = 0
        self._key = jax.random.PRNGKey(config.seed)
        self._host_len = np.zeros((slots,), np.int64)
        # Serving stats: tokens actually prefilled vs skipped via shared
        # prefix pages, the page pool's high-water mark, speculative
        # draft/accept counters, and step wall time (stats()).
        self.prefill_tokens = 0
        self.prefill_tokens_saved = 0
        self.peak_pages = 0
        self.spec_proposed = 0
        self.spec_accepted = 0
        # verify_passes counts verify *program launches* (one per engine
        # step with survivors, shared by every slot in the batch);
        # spec_rounds counts slot-level verify rounds — the number of
        # full model streams speculative work cost, the honest unit for
        # "verify passes per generated token" (a plain decode step costs
        # one stream per slot-round too).
        self.verify_passes = 0
        self.spec_rounds = 0
        self._step_sec = 0.0
        # Per-phase wall time (stats() exposes these; sec_per_token keeps
        # its historical total-step definition). Always accumulated — a
        # handful of perf_counter() calls per step, nanoseconds against
        # a millisecond-scale step.
        self._admit_sec = 0.0
        self._chunk_sec = 0.0
        self._draft_sec = 0.0
        self._verify_sec = 0.0
        self._decode_sec = 0.0
        self._step_idx = 0
        # Roofline cost model (serving/costmodel.py): modeled bytes and
        # FLOPs per phase, accumulated every step from the live state —
        # cheap host arithmetic, always on like the phase timers above.
        # stats()["roofline"] combines them with the phase wall times;
        # telemetry additionally gets the per-step breakdown.
        self.cost_model = CostModel.from_configs(model_cfg, config)
        self._phase_bytes = {p: 0.0 for p in
                             ("admit", "chunk_prefill", "draft",
                              "verify", "decode")}
        self._phase_flops = dict(self._phase_bytes)
        self._shape: Optional[StepShape] = None
        if self.telemetry.enabled:
            self.telemetry.attach_roofline(self.cost_model.describe())

        self.prefill_chunk_tokens = config.prefill_chunk_tokens
        # KV pool storage: "model" (compute dtype), "int8" (int8 pages
        # + scale rows, quantized at write time, dequantized in the
        # paged kernels) or "int4" (nibble-packed pages + bf16 scale
        # rows). None defers to the model config's kv_dtype.
        resolved_kv = config.resolved_kv_dtype(model_cfg)
        self.kv_cache_dtype = resolved_kv
        self.kv_scale_dtype = config.kv_scale_dtype
        self.spec = config.speculative
        self.drafter = (make_drafter(config.speculative, engine, max_len,
                                     telemetry=self.telemetry)
                        if config.speculative is not None else None)
        page_size, num_pages = config.page_size, config.num_pages
        max_pages = -(-max_len // page_size)
        self.page_bytes = kv.page_kv_bytes(model_cfg, page_size, resolved_kv,
                                           config.kv_scale_dtype)
        if num_pages is None:
            # The byte budget of every slot holding max_len tokens of
            # model-dtype KV (plus the trash page): int8 pages cost ~half
            # the bytes, so the same HBM holds ~2x the pages — double the
            # resident-request capacity at fixed memory, which is the
            # point of the int8 mode.
            budget = slots * max_pages * kv.page_kv_bytes(
                model_cfg, page_size, "model")
            num_pages = budget // self.page_bytes + 1
        self.allocator = kv.BlockAllocator(
            num_pages, page_size,
            prefix_sharing=config.prefix_sharing,
            telemetry=self.telemetry,
            pin_budget_pages=self.scheduler.pin_budget_pages)
        # With a mesh, the pools come back PartitionSpec-sharded over
        # their KV-head axis (kvcache.shard_cache wires
        # distributed.api.resolve_spec into the paged path); the block
        # tables and lengths live replicated so admission, COW forks,
        # rewind and swap stay host-side and global.
        self.cache = model_api.init_paged_cache(
            model_cfg, slots, num_pages, page_size, max_pages,
            kv_dtype=resolved_kv, kv_scale_dtype=config.kv_scale_dtype,
            mesh=self.mesh)
        if self.mesh is not None:
            # Weights and sampling state replicate across the mesh: only
            # the KV pools shard (the decode stream they gate is the
            # memory-bound part), and a replicated wo projection after
            # the exact head merge keeps outputs bit-identical — a
            # psum-merged sharded projection would reorder float adds.
            replicated = jax.sharding.NamedSharding(
                self.mesh, jax.sharding.PartitionSpec())
            self.params = jax.device_put(params, replicated)
            self.last_logits = jax.device_put(self.last_logits, replicated)

        # The jits wrap named functions so device traces name their
        # programs (`jit_decode_step`, `jit_prefill_chunk`,
        # `jit_verify_tokens`), not `jit__lambda`.
        # The cache is donated: decode and chunk-prefill steps update the
        # page pools in place instead of copying the whole buffer every
        # step (the engine never touches the stale pytree — it rebinds
        # self.cache from each call's result).
        # A MoE decode program also returns, per layer, how many experts
        # its live rows hit (a few bytes, fetched with the next step's
        # tokens for the `moe.*` counters).
        self._moe = model_cfg.family == "moe"
        self._expert_hits = None

        def decode_step(p, tok, cache):
            return model_api.decode_step(p, tok, cache, model_cfg, engine,
                                         expert_hits=self._moe)

        self._decode = jax.jit(decode_step, donate_argnums=(2,))

        # Paged prefill chunk: writes K/V straight into pool pages (and,
        # in int8 mode, their scale rows — donated alongside).
        def prefill_chunk(p, toks, bt, st, kp, vp, ksc, vsc):
            return model_api.prefill_chunk(p, toks, bt, st, kp, vp,
                                           model_cfg, engine, ksc, vsc)

        self._prefill_chunk = jax.jit(prefill_chunk,
                                      donate_argnums=(4, 5, 6, 7))

        # Speculative verify pass: score each slot's k+1 candidate
        # tokens in one prefill-chunk-shaped forward returning logits at
        # every position; pools donated exactly like _prefill_chunk.
        def verify_tokens(p, toks, bt, st, kp, vp, ksc, vsc):
            return model_api.verify_tokens(p, toks, bt, st, kp, vp,
                                           model_cfg, engine, ksc, vsc)

        self._verify = jax.jit(verify_tokens, donate_argnums=(4, 5, 6, 7))
        if self.mesh is not None:
            # Trace-time mesh activation: the attention layer keys its
            # shard_map dispatch off distributed.api.current_mesh(), so
            # every jitted step enters use_mesh(self.mesh) — after the
            # first trace this is a nanoseconds-scale context switch.
            self._decode = _under_mesh(self.mesh, self._decode)
            self._prefill_chunk = _under_mesh(self.mesh, self._prefill_chunk)
            self._verify = _under_mesh(self.mesh, self._verify)

    # Backward-compatible views of the scheduler-action counters; the
    # increments live in _Counters so they cannot drift from telemetry.
    @property
    def preemptions(self) -> int:
        return self._counters.preemptions

    @property
    def swap_outs(self) -> int:
        return self._counters.swap_outs

    @property
    def swap_ins(self) -> int:
        return self._counters.swap_ins

    def submit(self, prompt: np.ndarray, max_new_tokens: int = 32,
               priority: int = 0) -> int:
        prompt = np.asarray(prompt)
        if priority < 0:
            raise ValueError(f"priority must be >= 0, got {priority}")
        # The block-table width covers max_len tokens; writes past it
        # would be silently dropped. The chunked prefill path makes no
        # difference to the worst case — chunks land in the same
        # reserved pages — so validate the full footprint here, before
        # the request is queued and long before any pages are reserved.
        worst = kv.BlockAllocator.worst_case_tokens(len(prompt),
                                                   max_new_tokens)
        if worst > self.max_len:
            self.telemetry.count("admission.rejected.over_max_len")
            raise ValueError(
                f"request can occupy {worst} cache positions "
                f"(prompt {len(prompt)}, max_new {max_new_tokens}) "
                f"but max_len is {self.max_len}")
        # Gross worst-case pages must fit the pool: prefix sharing can
        # only shrink the bill while a sharer happens to be resident,
        # which admission cannot rely on — such a request would block
        # the FIFO head forever once the pool drains.
        need = self.allocator.pages_for(worst)
        usable = self.allocator.num_pages - 1
        if need > usable:
            self.telemetry.count("admission.rejected.over_pool_capacity")
            raise ValueError(
                f"request needs {need} pages worst case but the pool "
                f"has {usable}; no reservation was made")
        self._uid += 1
        self.queue.append(Request(self._uid, prompt, max_new_tokens,
                                  priority=priority))
        self.telemetry.request_submitted(self._uid, len(prompt),
                                         max_new_tokens, priority=priority)
        return self._uid

    # -- placement / preemption mechanisms (policy lives in scheduler.py) ---
    def _place(self, slot: int, req: Request, shared_tokens: int):
        """Install an admitted request into a slot. The allocator
        already mapped its prompt pages; the prompt's KV is produced
        chunk-by-chunk by _prefill_tick. A shared prefix just advances
        the cursor (a fully covered prompt recomputes its last token so
        its logits can feed sampling; that chunk COW-forks the shared
        page it writes into)."""
        req.shared_prompt_tokens = shared_tokens
        req.prefill_cursor = min(shared_tokens, len(req.prompt) - 1)
        self.prefill_tokens_saved += req.prefill_cursor
        self._host_len[slot] = 0
        self.telemetry.request_admitted(req.uid, slot, shared_tokens)
        self.active[slot] = req

    def _admit_queued(self, req: Request, slot: int,
                      reserve: bool = True) -> bool:
        """Try to admit a queued request into `slot`; False when
        the pool refuses. Used by skip-capable schedulers — `req` need
        not be the queue head."""
        res = self.allocator.admit_tokens(
            req.uid, req.prompt, req.max_new_tokens, reserve=reserve)
        if res is None:
            return False
        self.queue.remove(req)
        self._place(slot, req, res[1])
        return True

    def _preemptable(self, slot: int) -> bool:
        """A decoding slot can always be preempted (its pages are fully
        written through host_len, so the swap blob is exact). A
        mid-prefill slot can only be *aborted*, and only while no sharer
        holds its registered pages — pages past the borrowed prefix with
        refcount > 1 are content other admitted requests mapped and are
        still waiting for this donor to write."""
        req = self.active[slot]
        if req is None:
            return False
        if not req.prefilling:
            return True
        a = self.allocator
        borrowed = req.shared_prompt_tokens // a.page_size
        return all(a.refcount(p) <= 1
                   for p in a.pages_of(req.uid)[borrowed:])

    def _prefix_ready(self, slot: int) -> bool:
        """True when every prefix page this slot borrowed at admission
        has been fully written by its registrant — i.e. no active
        mid-prefill request still owes content to a page this slot
        mapped. Schedulers that reorder prefill (SLO) must not chunk a
        sharer before this holds; FIFO's strict uid order implies it."""
        req = self.active[slot]
        if req is None or req.shared_prompt_tokens == 0:
            return True
        a = self.allocator
        ps = a.page_size
        borrowed = set(a.pages_of(req.uid)[:req.shared_prompt_tokens // ps])
        for r in self.active:
            if r is None or r is req or not r.prefilling:
                continue
            own_from = r.shared_prompt_tokens // ps
            pages = a.pages_of(r.uid)
            for j in range(own_from, len(pages)):
                if pages[j] in borrowed and r.prefill_cursor < (j + 1) * ps:
                    return False
        return True

    def _preempt(self, slot: int):
        """Preempt-and-swap mechanism. Decoding victims: gather their
        pages (payload + scale rows, bit-exact) to the host tier, save
        the logits row sampling resumes from, release the device pages.
        Mid-prefill victims are *aborted* instead — their pages are not
        all fully written, so a blob could capture garbage; prefill is
        recomputed on re-admission. Either way the drafter's per-slot
        state is dropped (the slot id will be reused) and the request
        joins `self.swapped` for the scheduler to re-admit."""
        req = self.active[slot]
        a = self.allocator
        if req.prefilling:
            # Unregister the incompletely written pages this request
            # registered at admission (sharers are excluded by
            # _preemptable), take back the saved-prefill credit, and
            # reset the cursor for a fresh prefill on re-admission.
            a.unregister(req.uid,
                         from_logical=req.shared_prompt_tokens // a.page_size)
            self.prefill_tokens_saved -= min(req.shared_prompt_tokens,
                                             len(req.prompt) - 1)
            req.prefill_cursor = 0
            req.shared_prompt_tokens = 0
            entry = SwappedRequest(req, 0)
            self.cache = kv.clear_slot(self.cache, slot)
        else:
            n_kv = int(self._host_len[slot])
            ids = a.pages_of(req.uid)
            self.cache, blob = kv.swap_out_slot(
                self.cache, slot, ids, n_kv)
            self.swap_tier.put(req.uid, blob)
            entry = SwappedRequest(req, n_kv,
                                   logits=np.asarray(self.last_logits[slot]),
                                   has_blob=True)
            req.shared_prompt_tokens = 0
            self._counters.swap_out(pages=len(ids))
        a.release(req.uid)
        self.active[slot] = None
        self._host_len[slot] = 0
        if self.drafter is not None:
            # Preempted slots drop drafter state: the slot id is about
            # to be reused; a draft-model drafter re-prefills its own
            # cache from the request context on re-contact.
            self.drafter.release(slot)
        req.preemptions += 1
        self._counters.preempt()
        self.swapped.append(entry)

    def _swap_in(self, entry: SwappedRequest, slot: int,
                 reserve: bool = True) -> bool:
        """Re-admit a preempted request. Aborted mid-prefill entries go
        through a fresh admission (prefill recomputed, prefix
        cache may re-hit); swapped decoding entries get fresh pages and
        their exact payload restored from the host tier, resuming
        bit-identically. False when the pool refuses."""
        req = entry.req
        a = self.allocator
        if not entry.has_blob:
            res = a.admit_tokens(req.uid, req.prompt, req.max_new_tokens,
                                 reserve=reserve)
            if res is None:
                return False
            self.swapped.remove(entry)
            self._place(slot, req, res[1])
            self._counters.readmit()
            return True
        n_map = a.pages_for(entry.n_kv)
        worst = a.pages_for(a.worst_case_tokens(len(req.prompt),
                                                req.max_new_tokens))
        pages = a.admit_restored(req.uid, n_map, worst, reserve=reserve)
        if pages is None:
            return False
        blob = self.swap_tier.pop(req.uid)
        self.cache = kv.swap_in_slot(self.cache, slot, pages, blob)
        if self.mesh is not None:
            # Swap-in scatters a host blob into the pools eagerly;
            # shard_cache is a no-op when propagation kept the mesh
            # placement and a reshard if it drifted — the sharding
            # invariant holds without forking the swap path.
            self.cache = kv.shard_cache(self.cache, self.mesh)
        self.last_logits = self.last_logits.at[slot].set(
            jnp.asarray(entry.logits))
        self._host_len[slot] = entry.n_kv
        self.active[slot] = req
        self.swapped.remove(entry)
        self._counters.swap_in(pages=n_map)
        return True

    def _ensure_decode_capacity(self):
        """Optimistic (non-reserved) scheduling: before sampling, make
        sure the free list covers every page the coming decode (or
        verify) round may map — one extend per slot crossing a page
        boundary (k+1 candidate positions with speculation) plus one
        fork where the write lands in a still-shared page. Reclaims
        pinned pages first, then preempts victims; runs before sampling
        so a victim's state is a clean resume point."""
        a = self.allocator
        ps = a.page_size
        span = 1 + (self.spec.k if self.spec is not None else 0)
        while True:
            need = 0
            for i, r in enumerate(self.active):
                if r is None or r.prefilling:
                    continue
                L = int(self._host_len[i])
                pages = a.pages_of(r.uid)
                need += max(a.pages_for(L + span) - len(pages), 0)
                logical = L // ps
                if logical < len(pages) and a.refcount(pages[logical]) > 1:
                    need += 1
            if a.free_pages >= need:
                return
            a.reclaim_pinned(need - a.free_pages)
            if a.free_pages >= need:
                return
            victim = self.scheduler.pick_victim(self, None)
            if victim is None:
                return
            self._preempt(victim)

    def _prefill_tick(self):
        """Run at most one prompt chunk (token-budgeted) for one
        mid-prefill slot — which one is the scheduler's call
        (`select_prefill_slot`; FIFO = oldest uid). The chunk's K/V goes
        straight into the slot's pool pages; earlier chunks are read
        back through the block table. The slot joins the decode batch
        only when the cursor reaches the end of the prompt.

        Under FIFO, slots prefill strictly in admission (uid) order.
        That makes the allocator's registration-at-admission of
        prefix-cache pages safe: a later request that maps a donor's
        pages cannot run its own first chunk — let alone decode — until
        the donor's whole prompt (every shared page's contents) has been
        written. Reordering schedulers must enforce the same invariant
        through `_prefix_ready`."""
        cand = [(r.uid, i) for i, r in enumerate(self.active)
                if r is not None and r.prefilling]
        if not cand:
            return
        slot = self.scheduler.select_prefill_slot(self, cand)
        req = self.active[slot]
        start = req.prefill_cursor
        budget = self.prefill_chunk_tokens or len(req.prompt)
        end = min(len(req.prompt), start + budget)
        ps = self.allocator.page_size
        # COW at chunk granularity: fork any still-shared *borrowed* page
        # this chunk writes into before the device write (only reachable
        # for the recomputed last token of a fully covered prompt — other
        # borrowed pages are full and the cursor starts past them). Pages
        # past the borrowed prefix are this request's own fresh pages:
        # writing them is safe at any refcount, because the write is
        # precisely the registered content later sharers mapped.
        borrowed = req.shared_prompt_tokens // ps
        fork_range = range(start // ps, min((end - 1) // ps + 1, borrowed))
        tel = self.telemetry
        with tel.annotation("prefill_prep"):
            if self.scheduler.preemptive:
                # Optimistic admission reserves nothing ahead: make sure
                # the free list covers this chunk's COW forks before
                # issuing them, preempting victims (never this slot) if
                # dry.
                forks = sum(
                    1 for logical in fork_range
                    if self.allocator.refcount(
                        self.allocator.pages_of(req.uid)[logical]) > 1)
                if forks > self.allocator.free_pages:
                    self.allocator.reclaim_pinned(
                        forks - self.allocator.free_pages)
                while forks > self.allocator.free_pages:
                    victim = self.scheduler.pick_victim(
                        self, None, protect=frozenset((slot,)))
                    if victim is None:
                        return   # retry next step
                    self._preempt(victim)
            for logical in fork_range:
                page = self.allocator.pages_of(req.uid)[logical]
                if self.allocator.refcount(page) > 1:
                    old, new = self.allocator.fork_page(req.uid, logical)
                    self.cache = kv.copy_page(self.cache, old, new)
            pages = self.allocator.pages_of(req.uid)
            row = np.full((self.cache.block_tables.shape[1],), kv.TRASH_PAGE,
                          np.int32)
            row[:len(pages)] = pages
            toks = jnp.asarray(req.prompt[start:end])[None]
            rows = jnp.asarray(row)[None]
            starts = jnp.asarray([start], jnp.int32)
        t0c = tel.now() if tel.enabled else 0.0
        with tel.annotation("prefill_chunk"):
            res = self._prefill_chunk(
                self.params, toks, rows, starts,
                self.cache.k_pages, self.cache.v_pages,
                self.cache.k_scale, self.cache.v_scale)
        with tel.annotation("prefill_commit"):
            if self.cache.quantized:
                logits1, nk, nv, nks, nvs = res
            else:
                (logits1, nk, nv), nks, nvs = res, None, None
            lengths, tables = self.cache.lengths, self.cache.block_tables
            req.prefill_cursor = end
            self.prefill_tokens += end - start
            if not req.prefilling:
                # Activate: only now does the slot become visible to the
                # shared decode program (row + device length + first
                # logits).
                lengths = lengths.at[slot].set(end)
                tables = tables.at[slot].set(jnp.asarray(row))
                self.last_logits = self.last_logits.at[slot].set(logits1[0])
                self._host_len[slot] = end
            self.cache = kv.PagedCache(lengths, tables, nk, nv, nks, nvs)
            self.peak_pages = max(self.peak_pages, self.allocator.used_pages)
            if self._shape is not None:
                self._shape.chunk = (start, end - start)
            if tel.enabled:
                tel.chunk(req.uid, t0c, tel.now(), end - start)
                # Table entries the chunk kernel's walk reads, over every
                # layer and its window, against the entries the row holds.
                tel.count("prefill.pages_walked", sum(
                    n * paged_pf_k.prefill_walk_pages(end, start, w, ps)
                    for w, n in self._layer_windows.items()))
                tel.count("prefill.pages_tabled",
                          self.cfg.n_layers * len(row))

    def _release(self, slot: int, req: Request):
        req.done = True
        self.finished.append(req)
        self.active[slot] = None    # slot released; queue refills next step
        self.allocator.release(req.uid)
        self.cache = kv.clear_slot(self.cache, slot)
        if self.drafter is not None:
            self.drafter.release(slot)
        self._host_len[slot] = 0
        self.telemetry.request_finished(req.uid)

    def _map_write_range(self, slot: int, req: Request, first: int,
                         n_writes: int):
        """Map/fork pages so KV writes at positions first..first+n-1 land
        in private physical pages: extend where the position falls off
        the mapped pages (reservations make this infallible), COW-fork
        any still-shared page a write would touch."""
        ps = self.allocator.page_size
        for pos in range(first, first + n_writes):
            if self.allocator.needs_extend(req.uid, pos):
                page = self.allocator.extend(req.uid)
                n_mapped = len(self.allocator.pages_of(req.uid))
                self._repoint(slot, n_mapped - 1, page)
            else:
                logical = pos // ps
                page = self.allocator.pages_of(req.uid)[logical]
                if self.allocator.refcount(page) > 1:
                    old, new = self.allocator.fork_page(req.uid, logical)
                    self.cache = kv.copy_page(self.cache, old, new)
                    self._repoint(slot, logical, new)

    def step(self) -> int:
        """One engine step: admit, run at most one prompt chunk, then one
        decode step (or, with `speculative`, one draft-verify round)
        across all fully-prefilled slots. Returns the amount of
        outstanding work (live decodes + mid-prefill slots + queue).

        Phase wall time (admit / chunk prefill / draft / verify /
        decode) accumulates into stats(); with telemetry enabled each
        step additionally records its phase split and the pool/queue
        gauges at the step boundary, and with `annotate` each phase is
        a profiler span (docs/observability.md)."""
        tel = self.telemetry
        t_start = time.perf_counter()
        self._step_idx += 1
        before = ((self._admit_sec, self._chunk_sec, self._draft_sec,
                   self._verify_sec, self._decode_sec)
                  if tel.enabled else None)
        self._shape = StepShape()
        try:
            with tel.step_annotation(self._step_idx):
                return self._step_inner()
        finally:
            dur = time.perf_counter() - t_start
            self._step_sec += dur
            # Price the step: modeled bytes/FLOPs per phase from what
            # actually ran (always on — host arithmetic over a handful
            # of ints; serving outputs are untouched).
            costs = self.cost_model.step_costs(self._shape)
            self._shape = None
            for phase, c in costs.items():
                self._phase_bytes[phase] += c.bytes
                self._phase_flops[phase] += c.flops
            if tel.enabled:
                a = self.allocator
                tel.record_step(
                    t_start, dur,
                    self._admit_sec - before[0],
                    self._chunk_sec - before[1],
                    self._draft_sec - before[2],
                    self._verify_sec - before[3],
                    self._decode_sec - before[4],
                    a.used_pages, a.free_pages, a.available_pages,
                    len(self.queue),
                    sum(1 for r in self.active
                        if r is not None and r.prefilling),
                    costs={p: (c.bytes, c.flops)
                           for p, c in costs.items()})

    def _step_inner(self) -> int:
        tel = self.telemetry
        t = time.perf_counter()
        with tel.annotation("admit"):
            self.scheduler.schedule_admissions(self)
        self._admit_sec += time.perf_counter() - t
        t = time.perf_counter()
        self._prefill_tick()
        self._chunk_sec += time.perf_counter() - t
        if self.scheduler.preemptive:
            # Optimistic admission: the pool must cover this round's
            # page extends/forks before sampling (may preempt).
            with tel.annotation("admit"):
                self._ensure_decode_capacity()
        n_prefilling = sum(1 for r in self.active
                           if r is not None and r.prefilling)
        ready = [i for i, r in enumerate(self.active)
                 if r is not None and not r.prefilling]
        parked = len(self.queue) + len(self.swapped)
        if not ready:
            return n_prefilling + parked
        if self.spec is not None:
            return self._spec_round(ready) + n_prefilling + parked
        t_dec = time.perf_counter()
        with tel.annotation("sample"):
            self._key, step_key = jax.random.split(self._key)
            toks = sample(self.last_logits, step_key,
                          temperature=self.gen.temperature,
                          top_k=self.gen.top_k)
        with tel.annotation("token_sync"):
            if tel.enabled and self._expert_hits is not None:
                host_toks, hits = jax.device_get((toks, self._expert_hits))
                tel.count("moe.experts_hit", int(hits.sum()))
                self._expert_hits = None
            else:
                host_toks = np.asarray(toks)
        with tel.annotation("emit"):
            mask = np.zeros((self.slots,), bool)
            t_emit = tel.now() if tel.enabled else 0.0
            for i in ready:
                req = self.active[i]
                req.generated.append(int(host_toks[i]))
                tel.tokens(req.uid, t_emit)
                if (len(req.generated) >= req.max_new_tokens
                        or (self.gen.stop_on_eos
                            and host_toks[i] == self.gen.eos_id)):
                    self._release(i, req)
                else:
                    mask[i] = True
        # Decode-step boundary: map a fresh page wherever the next write
        # position falls off the end of a slot's mapped pages
        # (reservations make this infallible for admitted requests), and
        # COW-fork any still-shared page the write would land in so the
        # append cannot leak into other sequences. Mid-prefill slots are
        # skipped — their device length is 0, so the decode append lands
        # in the trash page.
        with tel.annotation("page_map"):
            for i in range(self.slots):
                req = self.active[i]
                if req is None or req.prefilling:
                    continue
                self._map_write_range(i, req, int(self._host_len[i]), 1)
            self.peak_pages = max(self.peak_pages, self.allocator.used_pages)
        if tel.enabled:
            # Pages the decode kernel's walk DMAs, over every layer and
            # its window (idle and mid-prefill slots sit at length 0: one
            # page), against the pages the tables hold.
            lens, ps = self._host_len + 1, self.allocator.page_size
            tel.count("decode.pages_walked", sum(
                n * paged_k.walk_pages(lens, w, ps)
                for w, n in self._layer_windows.items()))
            tel.count("decode.pages_tabled",
                      self.cfg.n_layers * self.slots
                      * self.cache.block_tables.shape[1])
        with tel.annotation("decode_step"):
            out = self._decode(self.params, toks, self.cache)
        if self._moe:
            self.last_logits, self.cache, self._expert_hits = out
            if tel.enabled:
                c = self.cfg
                tel.count("moe.assignments",
                          int(mask.sum()) * c.top_k * c.n_layers)
                tel.count("moe.experts_held", c.n_layers * c.n_experts)
        else:
            self.last_logits, self.cache = out
        # Only live slots advance; released/empty slots stay parked at 0
        # (decode_step freezes zero-length slots on device too).
        self._host_len += mask
        if self._shape is not None:
            # Post-append resident lengths per live slot — what the
            # decode attention just read through the block table.
            self._shape.decode_ran = True
            self._shape.decode_lens = [
                int(x) for x, m in zip(self._host_len, mask) if m]
        self._decode_sec += time.perf_counter() - t_dec
        return int(mask.sum()) + n_prefilling + parked

    def _spec_round(self, ready: list[int]) -> int:
        """One draft-verify round over the fully-prefilled slots.

        t0 (the greedy token from last_logits) is free — no model call —
        exactly as in a plain step. Continuing slots then get up to
        spec.k drafted continuations, every candidate's KV is written
        into the slot's pages by ONE verify forward returning logits at
        all k+1 positions, and greedy acceptance commits the longest
        matching draft prefix. The rejected tail rolls back in-pool:
        host/device lengths rewind and now-empty tail pages return to
        the free list *and the slot's reservation* (watermark math
        unchanged). Emits 1..k+1 tokens per live slot per round, so
        verify passes per generated token is <= 1 by construction.

        Bit-identicality with speculation off: t0 is the same argmax;
        the verify logits at each accepted position are the same
        computation a decode step would have run there (same resident
        KV, same position, same kernel family — the chunked-prefill
        equivalence the repo already holds); and a draft is accepted
        only when it *equals* the argmax at its position. Rejected
        drafts never influence committed state: their KV is length-
        masked away and rewound before any later read.
        """
        k = self.spec.k
        tel = self.telemetry
        t_draft0 = time.perf_counter()
        t_round0 = tel.now() if tel.enabled else 0.0
        # Greedy t0 per ready slot (speculative mode is greedy-only, so
        # no PRNG key is consumed — matching the spec-off greedy path,
        # where sample() ignores its key at temperature 0).
        with tel.annotation("token_sync"):
            host_logits = np.asarray(self.last_logits)
        survivors: list[tuple[int, Request, int, np.ndarray]] = []
        with tel.annotation("draft"):
            for i in ready:
                req = self.active[i]
                t0 = int(np.argmax(host_logits[i]))
                req.generated.append(t0)
                if (len(req.generated) >= req.max_new_tokens
                        or (self.gen.stop_on_eos and t0 == self.gen.eos_id)):
                    tel.tokens(req.uid, t_round0)
                    self._release(i, req)
                    continue
                # KV positions this request may still occupy are bounded by
                # the watermark reservation (prompt + max_new - 1): with G
                # tokens generated and KV resident through position L-1
                # (L = prompt + G - 1 before t0's write), at most
                # max_new - G - 1 draft writes fit after t0's. Slots out of
                # draft room still verify — a 1-token verify row is exactly
                # a decode step run through the verify program.
                room = req.max_new_tokens - len(req.generated) - 1
                k_i = min(k, room)
                context = np.concatenate(
                    [req.prompt, np.asarray(req.generated, np.int64)])
                drafts = (np.asarray(self.drafter.propose(i, context, k_i))
                          if k_i > 0 else np.zeros((0,), np.int64))
                drafts = drafts[:k_i]
                req.proposed += len(drafts)
                self.spec_proposed += len(drafts)
                survivors.append((i, req, t0, drafts))
        # Drafting is host-side work (argmaxes + drafter.propose); its
        # cost must not be charged to the decode/verify phase.
        self._draft_sec += time.perf_counter() - t_draft0
        if not survivors:
            return 0
        t_ver0 = time.perf_counter()
        # Build the (slots, k+1) verify batch. Slots outside `survivors`
        # (empty, mid-prefill, or just released) keep all-trash block
        # table rows, so their padded rows scribble into the trash page
        # and their logits are ignored.
        with tel.annotation("page_map"):
            tokens = np.zeros((self.slots, k + 1), np.int32)
            starts = np.zeros((self.slots,), np.int32)
            for i, req, t0, drafts in survivors:
                L = int(self._host_len[i])
                tokens[i, 0] = t0
                tokens[i, 1:1 + len(drafts)] = drafts
                starts[i] = L
                if self._shape is not None:
                    self._shape.verify.append((L, 1 + len(drafts)))
                    if self.spec.mode == "draft-model":
                        # One draft forward per proposed token (the k-th
                        # draft is free; catch-up forwards roughly cover
                        # it — the weight stream is the dominant term).
                        self._shape.draft_forwards += len(drafts)
                # Map pages for every candidate write (t0 + drafts); padded
                # positions past the drafts either land in the tail of an
                # already-mapped page (dead data past the rewind length) or
                # fall off mapped pages into the trash page.
                self._map_write_range(i, req, L, 1 + len(drafts))
            self.peak_pages = max(self.peak_pages, self.allocator.used_pages)
        with tel.annotation("verify_tokens"):
            res = self._verify(
                self.params, jnp.asarray(tokens), self.cache.block_tables,
                jnp.asarray(starts), self.cache.k_pages, self.cache.v_pages,
                self.cache.k_scale, self.cache.v_scale)
        if self.cache.quantized:
            vlogits, nk, nv, nks, nvs = res
        else:
            (vlogits, nk, nv), nks, nvs = res, None, None
        self.cache = kv.PagedCache(
            self.cache.lengths, self.cache.block_tables, nk, nv, nks, nvs)
        self.verify_passes += 1
        self.spec_rounds += len(survivors)
        # Acceptance needs only the argmaxes: reduce on device and move
        # a (slots, k+1) int array to host instead of the full logits.
        with tel.annotation("token_sync"):
            greedy = np.asarray(jnp.argmax(vlogits, axis=-1))
        with tel.annotation("emit"):
            live = 0
            t_acc = tel.now() if tel.enabled else 0.0
            updates: list[tuple[int, int]] = []          # (slot, accepted)
            for i, req, t0, drafts in survivors:
                a, hit_eos = greedy_accept(
                    drafts, greedy[i], eos_id=self.gen.eos_id,
                    stop_on_eos=self.gen.stop_on_eos)
                for tok in drafts[:a]:
                    req.generated.append(int(tok))
                req.accepted += a
                self.spec_accepted += a
                if tel.enabled:
                    # 1 + a tokens commit together — a genuine burst, so the
                    # intra-round inter-token deltas are recorded as zeros.
                    tel.tokens(req.uid, t_acc, 1 + a)
                    tel.spec_round(req.uid, t_round0, t_acc, len(drafts), a)
                new_len = int(starts[i]) + 1 + a
                if hit_eos:
                    self._release(i, req)
                    continue
                # In-pool rollback of the rejected tail: host/device lengths
                # rewind to the accepted frontier and tail pages that are
                # now empty return to the free list + reservation.
                self.allocator.rewind(req.uid, new_len)
                keep = len(self.allocator.pages_of(req.uid))
                self.cache = kv.rewind_slot(self.cache, i, new_len, keep)
                self._host_len[i] = new_len
                updates.append((i, a))
                live += 1
            if updates:
                # One scatter: each live slot's next-round logits are the
                # verify logits after its last accepted token.
                rows = jnp.asarray([i for i, _ in updates])
                cols = jnp.asarray([a for _, a in updates])
                self.last_logits = self.last_logits.at[rows].set(
                    vlogits[rows, cols])
        self._verify_sec += time.perf_counter() - t_ver0
        return live

    def _repoint(self, slot: int, logical: int, page: int):
        self.cache = kv.PagedCache(
            lengths=self.cache.lengths,
            block_tables=self.cache.block_tables.at[slot, logical].set(page),
            k_pages=self.cache.k_pages,
            v_pages=self.cache.v_pages,
            k_scale=self.cache.k_scale,
            v_scale=self.cache.v_scale,
        )

    def run(self, max_steps: int = 10000) -> list[Request]:
        """Drive steps until drained; returns requests finished during
        this call (admitted-but-unfinished work is never dropped)."""
        start = len(self.finished)
        for _ in range(max_steps):
            n = self.step()
            if (n == 0 and not self.queue and not self.swapped
                    and all(a is None for a in self.active)):
                break
        return self.finished[start:]

    def stats(self) -> dict:
        """Aggregate serving stats over everything this engine has run.

        tokens / tokens_budget / sec_per_token mirror `generate()`'s
        accounting (tokens = emitted, budget = sum of request budgets,
        sec_per_token = total step wall time over emitted tokens);
        proposed / accepted / acceptance_rate / verify_passes /
        spec_rounds describe the speculative rounds (proposed and
        accepted sum the per-request counters exactly).
        verify_per_token = slot-level verify rounds per emitted token —
        the model-streams-per-token cost (a non-speculative engine pays
        exactly one decode stream per slot-round, so < 1 here means
        speculation genuinely amortized the memory-bound stream);
        tokens_per_pass = its inverse, 1 + the average accepted drafts
        per round. With speculation off every speculative field is 0.

        Phase split: step wall time decomposes into admit_sec
        (admission), chunk_prefill_sec (prompt chunks), draft_sec
        (host-side drafting — argmaxes + drafter.propose), verify_sec
        (the verify forward + acceptance/rollback), and decode_sec (the
        plain decode path: sampling + page mapping + the decode program).
        sec_per_token keeps its historical whole-step definition;
        model_sec_per_token charges only the model-stream phases
        (decode + verify), so host-side draft time no longer inflates
        the decode metric.

        Every ratio field reports 0.0 when its denominator is zero (an
        empty or all-rejected drain) instead of dividing step time by a
        fake one-token floor — `_ratio` below, regression-tested.

        Scheduler fields: `scheduler` (policy name), `preemptions` /
        `swap_outs` / `swap_ins` (lifetime decision counts), `swapped`
        (requests parked off-device right now), `swap_bytes_peak` (host
        tier high-water mark), `pinned_pages` (prefix pages alive at
        refcount 0 right now).
        """
        def _ratio(num, den):
            return num / den if den else 0.0

        reqs = (self.finished + [r for r in self.active if r is not None]
                + [e.req for e in self.swapped])
        tokens = sum(len(r.generated) for r in reqs)
        spec_tokens = tokens if self.spec is not None else 0
        return {
            "tokens": tokens,
            "tokens_budget": sum(r.max_new_tokens for r in reqs),
            "sec_per_token": _ratio(self._step_sec, tokens),
            "step_sec": self._step_sec,
            "admit_sec": self._admit_sec,
            "chunk_prefill_sec": self._chunk_sec,
            "draft_sec": self._draft_sec,
            "verify_sec": self._verify_sec,
            "decode_sec": self._decode_sec,
            "model_sec_per_token": _ratio(
                self._decode_sec + self._verify_sec, tokens),
            "prefill_tokens": self.prefill_tokens,
            "prefill_tokens_saved": self.prefill_tokens_saved,
            "peak_pages": self.peak_pages,
            "proposed": self.spec_proposed,
            "accepted": self.spec_accepted,
            "acceptance_rate": _ratio(self.spec_accepted,
                                      self.spec_proposed),
            "verify_passes": self.verify_passes,
            "spec_rounds": self.spec_rounds,
            "verify_per_token": _ratio(self.spec_rounds, spec_tokens),
            "tokens_per_pass": _ratio(spec_tokens, self.spec_rounds),
            "scheduler": self.scheduler.name,
            "preemptions": self.preemptions,
            "swap_outs": self.swap_outs,
            "swap_ins": self.swap_ins,
            "swapped": len(self.swapped),
            "swap_bytes_peak": self.swap_tier.bytes_peak,
            "pinned_pages": self.allocator.pinned_pages,
            "roofline": self._roofline_stats(),
        }

    def _roofline_stats(self) -> dict:
        """Per-phase roofline summary over everything this engine has
        run, from the always-on modeled-traffic accumulators and phase
        wall-times: modeled bytes/FLOPs, achieved GB/s, arithmetic
        intensity, and the memory/compute-bound classification against
        the cost model's hardware spec. Phases that never ran are
        omitted. The telemetry snapshot carries the windowed,
        per-step-resolved version of the same numbers."""
        sec = {"admit": self._admit_sec,
               "chunk_prefill": self._chunk_sec,
               "draft": self._draft_sec,
               "verify": self._verify_sec,
               "decode": self._decode_sec}
        hw = self.cost_model.hardware
        out = {}
        for phase, nbytes in self._phase_bytes.items():
            if nbytes <= 0.0:
                continue
            nflops = self._phase_flops[phase]
            s = sec[phase]
            intensity = nflops / nbytes
            out[phase] = {
                "modeled_bytes": nbytes,
                "modeled_flops": nflops,
                "sec": s,
                "achieved_gbps": nbytes / s / 1e9 if s else 0.0,
                "arithmetic_intensity": intensity,
                "bound": hw.classify(intensity),
            }
        return out
