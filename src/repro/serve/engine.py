"""ServeEngine — request-level serving with continuous batching.

The engine turns the model zoo's prefill/decode steps into a *service*:
callers ``submit()`` :class:`Request` objects at any time, drive the engine
with ``step()`` (one scheduling round: admit waiting requests into free KV
slots, then one fused decode step for every active slot) or
``run_until_idle()``, and consume streaming :class:`Token` events plus a
final :class:`Completion` per request.

Design points, each load-bearing for the paper's "committed pattern in
operation" end state:

* **Continuous batching** — the KV cache is ``n_slots`` batch rows with
  *per-slot* write positions (``cache["index"]`` is (B,)); finished
  requests free their slot mid-flight and the next waiting request is
  prefilled straight into it while the other slots keep decoding.  A
  token budget (:class:`repro.serve.scheduler.Scheduler`) bounds how much
  prefill work any single step may inject ahead of the in-flight decodes.
* **Block-paged KV cache** — with ``page_size`` set, slot storage moves
  into a shared :class:`repro.serve.kv.PagePool`: K/V lives in fixed-size
  pages, each slot holds a page list (:class:`repro.serve.kv.PageTable`),
  and the decode program gathers K/V *through the page table*, which it
  receives as a traced ``(n_slots, max_pages)`` operand — admissions,
  evictions and page appends never retrace.  Capacity becomes
  ``n_pages x page_size`` shared tokens instead of a per-request
  ``max_len`` reservation; under page pressure the youngest request is
  preempted (pages reclaimed, request requeued, continuation
  token-identical).  ``page_size=max_len`` is the degenerate
  one-page-per-slot case — the contiguous layout as a special case of the
  paged one.
* **Chunked prefill** — ``prefill_chunk`` splits prompts longer than one
  chunk into chunk-sized pieces run on consecutive engine steps,
  interleaved with the in-flight decodes (pages allocated per chunk), so
  one long prompt no longer spikes every other request's inter-token
  latency or TTFT.
* **Plan-aware phase dispatch** — prefill and decode are *different
  programs* with different winning offload patterns, so each phase is
  traced under its own committed plan (``zoo:<arch>:prefill`` /
  ``zoo:<arch>:decode`` from a :class:`PlanStore`), bound with zero
  re-measurement exactly like ``OffloadSession.attach``.
* **Fused sampling** — logits never leave the device: the jitted phase
  programs end in :func:`repro.serve.sampler.sample_tokens`, so the
  per-step host transfer is (B,) token ids, not (B, V) logits.
* **Telemetry** — every phase call runs under ``metering.meter_window``
  and aggregates into per-phase :class:`PhaseTelemetry`; the decode loop
  feeds a ``runtime.StepMonitor``; :meth:`ServeEngine.metrics` reports
  KV-pool utilization, stranded capacity and page fragmentation.
"""

from __future__ import annotations

import contextlib
import dataclasses
import time
from typing import Any, Iterable, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.configs.base import ArchConfig
from repro.core import blocks as blocks_mod
from repro.kernels.paged_attention import block_tokens, walk_blocks
from repro.metering import meter_window, resolve_meter
from repro.metering.meters import WindowTelemetry
from repro.models import lm
from repro.models.attention import cache_seq_axes, insert_pages
from repro.obs import MetricsRegistry, Tracer, get_tracer
from repro.offload import stored_binding
from repro.runtime.monitor import StepMonitor
from repro.serve.kv import PagePool, PageTable, PoolExhausted, pages_for
from repro.serve.request import Completion, Request, RequestState, Token
from repro.serve.sampler import Sampler, sample_tokens
from repro.serve.scheduler import Scheduler, request_track

PHASES = ("prefill", "decode")


@dataclasses.dataclass
class PhaseTelemetry:
    """Aggregate of every ``meter_window`` a phase ran under.

    With a ``registry`` (a :class:`repro.obs.MetricsRegistry`) attached,
    every :meth:`add` *also* writes through to the
    ``serve_phase_{calls,seconds,tokens,joules}_total{phase=...}``
    counters — one observation feeds both views, so the legacy aggregate
    and the exported metrics can never disagree.  The dataclass fields
    remain the compatibility surface; new consumers should read the
    registry.
    """

    phase: str
    calls: int = 0
    seconds: float = 0.0
    tokens: int = 0
    joules: float | None = None
    provenance: str | None = None
    registry: Any = dataclasses.field(
        default=None, repr=False, compare=False
    )

    def __post_init__(self) -> None:
        self._counters = None
        if self.registry is not None:
            lab = {"phase": self.phase}
            reg = self.registry
            self._counters = (
                reg.counter(
                    "serve_phase_calls_total",
                    "phase program invocations", ("phase",),
                ).labels(**lab),
                reg.counter(
                    "serve_phase_seconds_total",
                    "wall seconds inside phase programs", ("phase",),
                ).labels(**lab),
                reg.counter(
                    "serve_phase_tokens_total",
                    "tokens processed per phase", ("phase",),
                ).labels(**lab),
                reg.counter(
                    "serve_phase_joules_total",
                    "metered energy per phase", ("phase",),
                ).labels(**lab),
            )

    def add(self, tele: WindowTelemetry, tokens: int) -> None:
        self.calls += 1
        self.seconds += tele.seconds
        self.tokens += tokens
        if tele.joules is not None:
            self.joules = (self.joules or 0.0) + tele.joules
            self.provenance = tele.provenance
        if self._counters is not None:
            calls_c, seconds_c, tokens_c, joules_c = self._counters
            calls_c.inc()
            seconds_c.inc(max(tele.seconds, 0.0))
            tokens_c.inc(tokens)
            if tele.joules is not None:
                joules_c.inc(max(tele.joules, 0.0))

    @property
    def tokens_per_second(self) -> float:
        return self.tokens / self.seconds if self.seconds else 0.0

    @property
    def joules_per_token(self) -> float | None:
        if self.joules is None or not self.tokens:
            return None
        return self.joules / self.tokens

    def summary(self) -> str:
        out = (
            f"{self.phase}: {self.tokens} tok in {self.seconds:.2f}s "
            f"({self.tokens_per_second:.1f} tok/s, {self.calls} calls)"
        )
        if self.joules is not None:
            out += (
                f", {self.joules:.1f} J"
                f" [{self.joules_per_token:.3g} J/tok, {self.provenance}]"
            )
        return out


@dataclasses.dataclass(frozen=True)
class EngineStats:
    """One engine lifetime in numbers."""

    steps: int
    requests_submitted: int
    requests_completed: int
    prefill_calls: int
    decode_steps: int
    tokens_generated: int
    slot_reuses: int
    max_active: int
    preemptions: int = 0
    prefill_chunks: int = 0


@dataclasses.dataclass
class _PrefillProgress:
    """One request mid-chunked-prefill: the per-request working cache and
    how much of the context has been extended into it."""

    state: RequestState
    context: list[int]
    cache: Any
    pos: int = 0


class ServeEngine:
    """Request-level serving engine over the block-pattern LM.

    ``cfg`` is an :class:`ArchConfig` (or an arch name, resolved through
    ``get_config``).  ``plan_dir``/``plan_keys`` bind each phase to a
    committed offload plan: with ``plan_dir`` alone the stored
    ``zoo:<arch>:prefill`` / ``zoo:<arch>:decode`` plans apply when
    present (and compatible with this environment); ``plan_keys`` may name
    explicit keys per phase or one key for both.  ``sampler`` is the
    default :class:`Sampler` for requests that don't carry their own.
    ``meter`` (name or ``PowerMeter``) adds per-phase energy telemetry.

    ``page_size`` switches the KV cache to the block-paged layout;
    ``n_pages`` sizes the shared pool (default: capacity-equivalent to
    the contiguous layout, ``n_slots * ceil(max_len / page_size)``).
    Admission then gates on free pages, eviction returns pages, and a
    smaller pool *over-commits*: more slots than the pool could hold at
    worst case, safe because the youngest request is preempted (and later
    resumed token-identically) if the pool ever actually fills.

    ``prefill_chunk`` enables chunked prefill (attention-family archs
    only — a recurrent SSM scan cannot resume across chunk boundaries):
    prompts longer than the chunk extend the cache chunk-by-chunk on
    consecutive steps, interleaved with running decodes.

    ``prefill_bucket`` pads prompts up to a multiple of the bucket so
    prefill traces are shared across prompt lengths — attention-family
    archs only (padded tokens would corrupt a recurrent SSM state; the
    padded KV rows here are provably never attended: each decode step
    overwrites position ``index`` before the mask ever admits it).
    """

    def __init__(
        self,
        cfg: ArchConfig | str,
        *,
        params: Any = None,
        n_slots: int = 4,
        max_len: int = 256,
        sampler: Sampler | None = None,
        meter: Any = None,
        plan_dir: str | None = None,
        plan_keys: "dict[str, str | None] | str | None" = None,
        max_tokens_per_step: int | None = None,
        prefill_bucket: int | None = None,
        prefill_chunk: int | None = None,
        page_size: int | None = None,
        n_pages: int | None = None,
        decode_impl: str = "auto",
        kv_validate: bool = False,
        monitor: StepMonitor | None = None,
        tracer: Tracer | None = None,
        registry: MetricsRegistry | None = None,
        seed: int = 0,
        quiet: bool = True,
    ) -> None:
        if isinstance(cfg, str):
            cfg = get_config(cfg)
        if cfg.frontend == "patch_embed":
            raise ValueError(
                f"{cfg.name}: patch-embed frontends have no token prompt "
                "path; the serving engine takes token-id requests"
            )
        if prefill_bucket is not None and "m" in cfg.pattern():
            raise ValueError(
                "prefill_bucket pads prompts, which corrupts recurrent SSM "
                f"state — unsupported for '{cfg.name}' "
                f"(pattern {cfg.pattern()!r})"
            )
        if prefill_chunk is not None and "m" in cfg.pattern():
            raise ValueError(
                "prefill_chunk resumes the sequence mid-prompt, which an "
                f"SSM scan cannot do — unsupported for '{cfg.name}' "
                f"(pattern {cfg.pattern()!r})"
            )
        if prefill_chunk is not None and prefill_chunk < 1:
            raise ValueError("prefill_chunk must be >= 1")
        if n_pages is not None and page_size is None:
            raise ValueError("n_pages given without page_size")
        if decode_impl not in ("auto", "xla", "pallas"):
            raise ValueError(
                f"decode_impl must be auto|xla|pallas, got {decode_impl!r}"
            )
        if decode_impl != "auto" and page_size is None:
            raise ValueError(
                "decode_impl pins the paged_attention binding — it requires "
                "the paged KV cache (page_size)"
            )
        self.decode_impl = decode_impl
        self.cfg = cfg
        self.n_slots = n_slots
        self.max_len = max_len
        self.sampler = sampler or Sampler.greedy()
        self.meter = resolve_meter(meter)
        self.seed = seed
        self.quiet = quiet
        self.prefill_bucket = prefill_bucket
        self.prefill_chunk = prefill_chunk

        # -- observability -------------------------------------------------
        # tracer: request-lifecycle spans (defaults to the process tracer,
        # a disabled no-op unless someone enabled it); registry: the
        # metric families every telemetry write-through lands in
        self.tracer = tracer if tracer is not None else get_tracer()
        self.registry = registry if registry is not None else MetricsRegistry()
        self._queue_depth_g = self.registry.gauge(
            "serve_queue_depth", "requests waiting for a slot"
        )
        self._active_slots_g = self.registry.gauge(
            "serve_active_slots", "requests resident in KV slots"
        )
        self._kv_util_g = self.registry.gauge(
            "serve_kv_utilization_pct", "KV pool/slot utilization"
        )
        self._kv_stranded_g = self.registry.gauge(
            "serve_kv_stranded_pct", "reserved-but-unused KV capacity"
        )
        self._kv_frag_g = self.registry.gauge(
            "serve_kv_fragmentation_pct", "partial-page fragmentation"
        )
        self._submitted_c = self.registry.counter(
            "serve_requests_submitted_total", "requests accepted by submit()"
        )
        self._completed_c = self.registry.counter(
            "serve_requests_completed_total", "requests finished"
        )
        self._generated_c = self.registry.counter(
            "serve_tokens_generated_total", "tokens sampled across requests"
        )
        self._step_hist = self.registry.histogram(
            "serve_step_seconds", "fused decode step latency"
        )
        self._moe_pairs_c = self._moe_busiest_c = None
        if cfg.moe is not None:
            # summed over decode calls and MoE layers; their ratio times
            # the held experts is a step's busiest/mean expert load
            self._moe_pairs_c = self.registry.counter(
                "serve_moe_pairs_total",
                "(token, held expert) pairs the decode steps computed, "
                "over every MoE layer",
            )
            self._moe_busiest_c = self.registry.counter(
                "serve_moe_busiest_total",
                "the busiest held expert's pairs of each decode step, "
                "summed over the MoE layers",
            )
        self.monitor = monitor or StepMonitor()
        if self.monitor.histogram is None:
            self.monitor.histogram = self._step_hist

        # -- KV memory subsystem ------------------------------------------
        self.paged = page_size is not None
        if self.paged:
            if page_size < 1:
                raise ValueError("page_size must be >= 1")
            max_pages = pages_for(max_len, page_size)
            if n_pages is None:
                # capacity-equivalent default: the paged layout holds the
                # same tokens as the contiguous one, minus the stranding
                n_pages = n_slots * max_pages
            self.kv: PageTable | None = PageTable(
                n_slots, max_pages, PagePool(n_pages, page_size),
                validate=kv_validate,
            )
            self._slot_len = max_pages * page_size
            self._seq_axes = cache_seq_axes(cfg)
            self._group_kinds = {g.key: g.kind for g in lm.groups_of(cfg)}
            self.cache = lm.init_cache(
                cfg, n_slots, max_len, page_size=page_size, n_pages=n_pages
            )
            # (layers, block tokens) of each attention group's page walk,
            # sized as the paged_attention kernel sizes it from a token row
            # of its K pool (MLA's latent pool is its K)
            self._walks = []
            for key, kind in self._group_kinds.items():
                if kind == "m":
                    continue
                group = self.cache[key]
                pool = group["k"] if "k" in group else group["c"]
                layers, _, _, width = pool.shape
                token_bytes = width * pool.dtype.itemsize
                self._walks.append(
                    (layers, block_tokens(page_size, token_bytes, max_pages))
                )
            self._walk_c = self.registry.counter(
                "serve_paged_walk_blocks_total",
                "page blocks the decode steps' paged attention walks visit, "
                "over every attention layer",
            )
            self._walk_full_c = self.registry.counter(
                "serve_paged_walk_blocks_full_total",
                "page blocks those walks would visit over all max_pages",
            )
        else:
            self.kv = None
            self._slot_len = max_len
            self.cache = lm.init_cache(cfg, n_slots, max_len)

        self.scheduler = Scheduler(
            n_slots,
            max_tokens_per_step,
            prompt_cost=self._admission_cost,
            kv=self.kv,
            admit_tokens=self._admission_tokens,
            tracer=self.tracer,
            metrics=self.registry,
        )

        self.params = (
            params if params is not None else lm.init_params(cfg, seed=seed)
        )

        # -- plan-aware phase dispatch ------------------------------------
        # keys the caller named explicitly must fail loudly when they
        # cannot bind (mirrors resolve_meter: an explicit request is a
        # contract, not a hint); store-derived defaults degrade silently
        explicit = plan_keys is not None
        if explicit and not plan_dir:
            raise ValueError(
                "plan_keys given without plan_dir — both are required to "
                "bind a committed plan"
            )
        self.plan_keys = self._resolve_plan_keys(plan_dir, plan_keys)
        self._bindings: dict[str, dict[str, str] | None] = {}
        for phase in PHASES:
            key = self.plan_keys[phase]
            mapping = (
                stored_binding(plan_dir, key)
                if plan_dir and key
                else None
            )
            if key and mapping is None:
                if explicit:
                    raise ValueError(
                        f"plan '{key}' for phase '{phase}' not "
                        f"found/compatible in {plan_dir}"
                    )
                if not quiet:
                    print(
                        f"serve: plan '{key}' not found/compatible in "
                        f"{plan_dir}; {phase} runs on default bindings"
                    )
            elif mapping and not quiet:
                print(f"serve: {phase} bound to plan '{key}': {mapping}")
            self._bindings[phase] = mapping
        # an explicit decode_impl overrides whatever the stored decode plan
        # (or the default preference order) would pick for the hot loop's
        # paged_attention block; "auto" leaves the planner's choice alone
        if decode_impl != "auto":
            base = self._bindings.get("decode") or {}
            self._bindings["decode"] = {
                **base, "paged_attention": decode_impl,
            }

        # the cache arguments are donated: the old cache is dead the moment
        # a step returns its successor, and without donation every decode
        # step / admission would copy the full multi-layer KV cache.
        # Every jitted program registers with the repro.analysis hot-path
        # pass: the wrapper records each call's abstract signature so
        # engine.lint() can verify the PR-4/5 contracts (decode's host
        # transfer is token ids only, recomposition never retraces).
        from repro.analysis.hotpath import ProgramSet

        self.programs = ProgramSet()
        # the ProgramSet shares the engine's obs attachments: new-signature
        # calls run inside "serve.compile" spans and feed the retrace
        # counters
        self.programs.tracer = self.tracer
        self.programs.metrics = self.registry
        self._prefill_fn = self.programs.register(
            "prefill", jax.jit(self._build_prefill()),
            carry_outputs=(1,),  # the b1 cache goes to insert, not to host
        )
        self._decode_fn = self.programs.register(
            "decode", jax.jit(self._build_decode(), donate_argnums=(2,)),
            loop=True,
            carry_outputs=(1,),  # the donated successor cache stays on device
            expected_signatures=1,  # recomposing the batch must not retrace
        )
        self._insert_fn = self.programs.register(
            "insert",
            jax.jit(
                self._insert_slot_paged if self.paged else self._insert_slot,
                donate_argnums=(0,),
            ),
            carry_outputs=(0,),  # the whole output is the engine cache
            expected_signatures=1,  # slot recomposition must not retrace
        )
        self._extend_fn = self.programs.register(
            "extend", jax.jit(self._build_extend(), donate_argnums=(2,)),
            carry_outputs=(0,),
        )
        self._extend_sample_fn = self.programs.register(
            "extend_sample",
            jax.jit(self._build_extend_sample(), donate_argnums=(2,)),
            carry_outputs=(1,),
        )

        # host-side per-slot state mirrors (pushed each decode step)
        self._last_tok = np.zeros((n_slots, 1), np.int32)
        self._seeds = np.zeros((n_slots,), np.int32)
        self._gen_counts = np.zeros((n_slots,), np.int32)
        self._temps = np.zeros((n_slots,), np.float32)
        self._topks = np.zeros((n_slots,), np.int32)
        self._lengths = np.zeros((n_slots,), np.int64)  # resident tokens
        # the cache's per-row write positions: idle and mid-prefill rows
        # keep advancing with every decode step, as the device's do
        self._dev_index = np.zeros((n_slots,), np.int64)

        #: slots mid-chunked-prefill (slot -> _PrefillProgress); these
        #: occupy a slot + pages but are excluded from decode until the
        #: final chunk samples their first token
        self._prefilling: dict[int, _PrefillProgress] = {}
        # device-resident page-table operand, re-uploaded only when the
        # table actually changed (steady-state decode recomposes nothing)
        self._pages_op: jax.Array | None = None
        self._pages_version = -1

        self.telemetry = {
            p: PhaseTelemetry(p, registry=self.registry) for p in PHASES
        }
        self.completions: dict[int, Completion] = {}
        self._finished: list[Completion] = []
        self._next_id = 0
        self._submitted = 0
        self._steps = 0
        self._max_active = 0
        self._chunk_calls = 0
        # per-step KV-health samples (while requests were resident):
        # (utilization_pct, stranded_pct, fragmentation_pct) running sums
        self._kv_samples = 0
        self._kv_sums = [0.0, 0.0, 0.0]

    # -- admission policy ------------------------------------------------------
    @staticmethod
    def _ctx_len(state: RequestState) -> int:
        """Tokens of context an admission must (re-)prefill: the prompt,
        plus any tokens already generated before a preemption."""
        return len(state.request.prompt) + len(state.tokens)

    def _is_chunked(self, ctx: int) -> bool:
        return self.prefill_chunk is not None and ctx > self.prefill_chunk

    def _admission_cost(self, state: RequestState) -> int:
        """Budget tokens the admission's first program call runs."""
        ctx = self._ctx_len(state)
        if self._is_chunked(ctx):
            return self.prefill_chunk
        return self._padded_len(ctx)

    def _admission_tokens(self, state: RequestState) -> int:
        """Tokens the admission must hold pages for right now."""
        ctx = self._ctx_len(state)
        if self._is_chunked(ctx):
            return min(ctx, self.prefill_chunk)
        return ctx

    # -- plan resolution ------------------------------------------------------
    def _resolve_plan_keys(
        self,
        plan_dir: str | None,
        plan_keys: "dict[str, str | None] | str | None",
    ) -> dict[str, str | None]:
        if isinstance(plan_keys, str):
            return {p: plan_keys for p in PHASES}
        if plan_keys is not None:
            unknown = set(plan_keys) - set(PHASES)
            if unknown:
                raise KeyError(
                    f"unknown serve phases {sorted(unknown)}; known: {PHASES}"
                )
            return {p: plan_keys.get(p) for p in PHASES}
        if plan_dir:
            from repro.offload.zoo import default_plan_key

            # zoo plans are keyed by the *base* arch — a reduced config
            # (verification-environment shape) binds the same plans
            arch = self.cfg.name.removesuffix("-reduced")
            return {
                p: default_plan_key(plan_dir, arch, p) for p in PHASES
            }
        return {p: None for p in PHASES}

    def _phase(self, phase: str):
        mapping = self._bindings.get(phase)
        if not mapping:
            return contextlib.nullcontext()
        return blocks_mod.registry.bind(mapping)

    # -- jitted programs -------------------------------------------------------
    def _build_prefill(self):
        cfg = self.cfg
        cache_metas = lm.cache_metas_tree(cfg, 1, self._slot_len)

        def prefill_fn(params, tokens, last_idx, seed, gen_step, temp, topk):
            """tokens (1, Lp) -> (sampled token (1,), filled b1 cache).

            The zero cache is built *inside* the program (XLA fuses it to
            nothing), only the *last real position*'s hidden state reaches
            the head — the (1, Lp, V) logits tensor is never materialised
            — and padded bucket positions past ``last_idx`` are ignored.
            ``gen_step`` is the sampled token's generation index: 0 for a
            fresh request, ``len(tokens)`` when a preempted request
            resumes (the (seed, index) PRNG key must keep its place).
            """
            from repro.models import params as pm

            cache = pm.init_params(cache_metas, 0)
            x, _, new_cache, _ = lm.backbone(
                params, {"tokens": tokens}, cfg, "prefill", cache
            )
            x_last = jax.lax.dynamic_slice_in_dim(x, last_idx, 1, axis=1)
            logits = lm.head(params, x_last, cfg)[:, 0, : cfg.vocab_size]
            tok = sample_tokens(
                logits,
                seed[None],
                gen_step[None],
                temp[None],
                topk[None],
            )
            new_cache["index"] = (last_idx + 1)[None].astype(jnp.int32)
            return tok, new_cache

        return prefill_fn

    def _build_decode(self):
        cfg = self.cfg
        paged = self.paged

        def decode_fn(params, tokens, cache, pages, seeds, steps, temps, topks):
            """One fused (logits -> token) step for the whole slot batch.
            ``pages`` is the page-table operand (paged mode; unused
            otherwise) — recomposing the batch never retraces.  With MoE
            layers the step's expert load (pairs computed, busiest held
            expert's pairs, over the layers) follows the (B,) token ids:
            one transfer carries both."""
            if paged:
                cache = dict(cache, pages=pages)
            logits, new_cache, load = lm.decode_step(params, tokens, cfg, cache)
            new_cache.pop("pages", None)
            tok = sample_tokens(
                logits[:, 0, : cfg.vocab_size], seeds, steps, temps, topks
            )
            if cfg.moe is not None:
                tok = jnp.concatenate([tok, load])
            return tok, new_cache

        return decode_fn

    def _build_extend(self):
        cfg = self.cfg

        def extend_fn(params, tokens, cache):
            """One non-final prefill chunk: extend the per-request cache
            by ``tokens`` (1, C), no sampling, no head matmul."""
            _, _, new_cache, _ = lm.backbone(
                params, {"tokens": tokens}, cfg, "extend", cache
            )
            new_cache["index"] = cache["index"] + tokens.shape[1]
            return new_cache

        return extend_fn

    def _build_extend_sample(self):
        cfg = self.cfg

        def extend_sample_fn(
            params, tokens, cache, last_off, seed, gen_step, temp, topk
        ):
            """The final prefill chunk: extend, project only the last real
            position and sample the request's first token."""
            x, _, new_cache, _ = lm.backbone(
                params, {"tokens": tokens}, cfg, "extend", cache
            )
            x_last = jax.lax.dynamic_slice_in_dim(x, last_off, 1, axis=1)
            logits = lm.head(params, x_last, cfg)[:, 0, : cfg.vocab_size]
            tok = sample_tokens(
                logits, seed[None], gen_step[None], temp[None], topk[None]
            )
            new_cache["index"] = cache["index"] + last_off + 1
            return tok, new_cache

        return extend_sample_fn

    @staticmethod
    def _insert_slot(cache, b1_cache, slot, page_ids):
        """Write a batch-1 prefilled cache into slot ``slot`` of the engine
        cache.  Group leaves are (layers, B, ...); ``index`` is (B,).
        ``page_ids`` is unused (contiguous layout)."""
        out = {}
        for key, value in cache.items():
            if key == "index":
                out[key] = value.at[slot].set(b1_cache[key][0])
            else:
                out[key] = jax.tree.map(
                    lambda dst, src: dst.at[:, slot].set(src[:, 0]),
                    value,
                    b1_cache[key],
                )
        return out

    def _insert_slot_paged(self, cache, b1_cache, slot, page_ids):
        """Scatter a batch-1 prefilled cache into the page pool as whole
        pages (``page_ids`` is the slot's (max_pages,) page list; entries
        past the allocation absorb into the null page).  SSM state groups
        have no sequence axis — they stay slot-indexed."""
        out = {}
        for key, value in cache.items():
            if key == "index":
                out[key] = value.at[slot].set(b1_cache[key][0])
            elif self._group_kinds[key] == "m":
                out[key] = jax.tree.map(
                    lambda dst, src: dst.at[:, slot].set(src[:, 0]),
                    value,
                    b1_cache[key],
                )
            else:
                out[key] = {
                    leaf: insert_pages(
                        value[leaf],
                        b1_cache[key][leaf],
                        page_ids,
                        self._seq_axes[leaf],
                    )
                    for leaf in value
                }
        return out

    # -- public API ------------------------------------------------------------
    def submit(self, request: Request) -> int:
        """Queue a request; returns its request id.  Admission happens on a
        subsequent ``step()`` when a slot and token budget are available."""
        total = len(request.prompt) + request.max_new_tokens
        if total > self.max_len:
            raise ValueError(
                f"request needs {total} cache positions "
                f"(prompt {len(request.prompt)} + {request.max_new_tokens} "
                f"new) but slots hold max_len={self.max_len}"
            )
        if self.kv is not None and (
            self.kv.pages_needed(total) > self.kv.pool.n_pages
        ):
            raise ValueError(
                f"request needs {self.kv.pages_needed(total)} pages "
                f"(prompt {len(request.prompt)} + {request.max_new_tokens} "
                f"new at page_size={self.kv.pool.page_size}) but the pool "
                f"holds {self.kv.pool.n_pages} — it could never be resident"
            )
        request_id = self._next_id
        self._next_id += 1
        self._submitted += 1
        self._submitted_c.inc()
        seed = (
            request.seed
            if request.seed is not None
            else (self.seed * 1_000_003 + request_id) & 0x7FFFFFFF
        )
        submitted_at = time.perf_counter()
        if self.tracer.enabled:
            self.tracer.event(
                "submit", tid=request_track(request_id),
                request=request_id, prompt=len(request.prompt),
                max_new=request.max_new_tokens,
            )
        self.scheduler.enqueue(
            RequestState(
                request_id=request_id,
                request=request,
                slot=-1,
                seed=seed,
                submitted_at=submitted_at,
            )
        )
        return request_id

    def step(self) -> list[Token | Completion]:
        """One scheduling round: in-flight prefill chunks, admissions
        (a prefill — or a first chunk — each), then one fused decode step
        over every decodable slot.  Returns the streamed events —
        ``Token`` per generated token, ``Completion`` per finished request
        — in generation order.

        With an enabled tracer each part runs in a live ``serve.*`` span
        (and so a profiler annotation) inside ``serve.step``: ``chunk``,
        ``admit``, ``prefill``, ``insert``, ``first_token``, ``pages``,
        ``decode``, ``decode_wait``, ``emit``, ``kv_health``."""
        if not self.scheduler.has_work:
            return []
        self._steps += 1
        span = self.tracer.span
        with span("serve.step", step=self._steps):
            events: list[Token | Completion] = []
            decoding = sum(
                1 for slot in self.scheduler.active
                if slot not in self._prefilling
            )
            planned, reserved = self._plan_chunks(decoding)
            spent = decoding + sum(run for _, run in planned) + reserved
            for slot, run in planned:
                with span("serve.chunk", slot=slot, tokens=run):
                    self._run_chunk(slot, run, events)

            with span("serve.admit"):
                admitted = self.scheduler.admissions(spent=spent)
            # concurrency peaks right after admission, before same-step
            # finishes release their slots — sample it here, not at step end
            self._max_active = max(
                self._max_active, len(self.scheduler.active)
            )
            for state in admitted:
                events.extend(self._admit(state))
            if any(
                slot not in self._prefilling for slot in self.scheduler.active
            ):
                events.extend(self._decode_active())
            with span("serve.kv_health"):
                self._sample_kv_health()
                self._queue_depth_g.set(len(self.scheduler.waiting))
                self._active_slots_g.set(len(self.scheduler.active))
        return events

    def run_until_idle(self, max_steps: int | None = None) -> list[Completion]:
        """Drive ``step()`` until every submitted request has completed;
        returns the completions in finish order."""
        start = len(self._finished)
        steps = 0
        while self.scheduler.has_work:
            self.step()
            steps += 1
            if max_steps is not None and steps > max_steps:
                raise RuntimeError(
                    f"engine still busy after {max_steps} steps "
                    f"({len(self.scheduler.active)} active, "
                    f"{len(self.scheduler.waiting)} waiting)"
                )
        return self._finished[start:]

    def stream(
        self, requests: Iterable[Request]
    ) -> "Iterable[Token | Completion]":
        """Submit ``requests`` and yield events until idle (convenience)."""
        for request in requests:
            self.submit(request)
        while self.scheduler.has_work:
            yield from self.step()

    def reset_stats(self) -> None:
        """Zero every lifetime counter — telemetry, monitor, scheduler
        reuse accounting, completions — without touching the compiled
        programs or the cache.  For load generators that warm the traces
        up front and must not report the warmup as served traffic.  Only
        valid on an idle engine (no active or waiting requests)."""
        if self.scheduler.has_work:
            raise RuntimeError("reset_stats on a busy engine")
        # the registry resets in place (child handles stay valid — the
        # scheduler and phase-telemetry counters keep working) and the
        # tracer drops the warmup spans with the rest of the warmup stats
        self.registry.reset()
        self.tracer.clear()
        self.telemetry = {
            p: PhaseTelemetry(p, registry=self.registry) for p in PHASES
        }
        self.monitor = StepMonitor(
            window=self.monitor.window.maxlen or 32,
            threshold=self.monitor.threshold,
            patience=self.monitor.patience,
            on_straggler=self.monitor.on_straggler,
            histogram=self._step_hist,
        )
        self.scheduler.admitted_per_slot.clear()
        self.scheduler.preemptions = 0
        if self.kv is not None:
            self.kv.pool.peak_used = self.kv.pool.used_pages
        self.completions.clear()
        self._finished.clear()
        self._submitted = 0
        self._steps = 0
        self._max_active = 0
        self._chunk_calls = 0
        self._kv_samples = 0
        self._kv_sums = [0.0, 0.0, 0.0]

    @property
    def stats(self) -> EngineStats:
        return EngineStats(
            steps=self._steps,
            requests_submitted=self._submitted,
            requests_completed=len(self._finished),
            prefill_calls=self.telemetry["prefill"].calls,
            decode_steps=self.telemetry["decode"].calls,
            tokens_generated=sum(
                len(c.tokens) for c in self._finished
            ) + sum(
                len(s.tokens) for s in self.scheduler.active.values()
            ),
            slot_reuses=self.scheduler.slot_reuses,
            max_active=self._max_active,
            preemptions=self.scheduler.preemptions,
            prefill_chunks=self._chunk_calls,
        )

    def _kv_snapshot(self) -> tuple[float, float, float]:
        """(utilization %, stranded %, fragmentation %) right now."""
        if self.kv is not None:
            pool = self.kv.pool
            return (
                100.0 * pool.used_pages / pool.n_pages,
                self.kv.stranded_pct,
                self.kv.fragmentation_pct,
            )
        active = len(self.scheduler.active)
        resident = int(
            sum(self._lengths[slot] for slot in self.scheduler.active)
        )
        reserved = active * self.max_len
        return (
            100.0 * reserved / (self.n_slots * self.max_len),
            100.0 * (reserved - resident) / reserved if reserved else 0.0,
            0.0,
        )

    def _sample_kv_health(self) -> None:
        if not self.scheduler.active:
            return
        util, stranded, frag = self._kv_snapshot()
        self._kv_samples += 1
        self._kv_sums[0] += util
        self._kv_sums[1] += stranded
        self._kv_sums[2] += frag
        self._kv_util_g.set(util)
        self._kv_stranded_g.set(stranded)
        self._kv_frag_g.set(frag)

    def metrics(self) -> dict:
        """KV memory health: pool utilization, stranded capacity and page
        fragmentation (paged), or the contiguous equivalents — the numbers
        that justify (or size) the page pool.  The ``mean_*`` keys average
        one sample per engine step taken while requests were resident, so
        they describe the *served* traffic, not the idle end state."""
        active = len(self.scheduler.active)
        resident = int(
            sum(self._lengths[slot] for slot in self.scheduler.active)
        )
        n = max(self._kv_samples, 1)
        out: dict = {
            "mode": "paged" if self.paged else "contiguous",
            "n_slots": self.n_slots,
            "max_len": self.max_len,
            "active": active,
            "waiting": len(self.scheduler.waiting),
            "preemptions": self.scheduler.preemptions,
            "prefill_chunks": self._chunk_calls,
            "mean_utilization_pct": self._kv_sums[0] / n,
            "mean_stranded_pct": self._kv_sums[1] / n,
            "mean_fragmentation_pct": self._kv_sums[2] / n,
        }
        out["programs"] = self.programs.stats()
        if self.kv is not None:
            out["kv"] = self.kv.stats()
        else:
            # a contiguous slot strands its whole unused tail — the
            # number the page pool exists to reclaim
            util, stranded, _ = self._kv_snapshot()
            out["kv"] = {
                "token_capacity": self.n_slots * self.max_len,
                "resident_tokens": resident,
                "reserved_tokens": active * self.max_len,
                "utilization_pct": util,
                "stranded_pct": stranded,
            }
        return out

    def serve_metrics(self, port: int = 0, host: str = "127.0.0.1"):
        """Expose this engine's :class:`~repro.obs.MetricsRegistry` over
        HTTP (Prometheus text format at ``/metrics``) on a daemon thread.
        ``port=0`` picks a free port.  Returns the
        :class:`~repro.obs.MetricsServer`; call ``.close()`` to stop it."""
        from repro.obs import MetricsServer

        return MetricsServer(self.registry, port=port, host=host)

    def profile_steps(self, n_steps: int, logdir: str) -> bool:
        """Drive ``step()`` ``n_steps`` times under a ``jax.profiler``
        capture window written to ``logdir``.  Returns False (and still
        runs the steps) when the profiler is unavailable — the window is
        opt-in observability, never a hard dependency."""
        from repro.obs import profile_window

        with profile_window(
            logdir, tracer=self.tracer, name="serve-steps"
        ) as captured:
            for _ in range(n_steps):
                if not self.scheduler.has_work:
                    break
                self.step()
        return captured

    def lint(self, envelope: Any = None) -> list:
        """Run the ``repro.analysis`` hot-path pass over every program this
        engine has actually called (host-sync, retrace drift, callbacks,
        constant capture) plus the page-aliasing sanitizer over the current
        page-table operand.  With ``envelope`` (a ``DeviceEnvelope`` or
        static-table name), the static capacity plan's verdict joins the
        diagnostics — a deployment that cannot fit is a ratchetable
        ``capacity-oom`` warning.  Returns the diagnostics; empty means
        the serving contracts hold for the traffic served so far."""
        from repro.analysis.paging import check_page_table

        diags = list(self.programs.lint())
        if self.kv is not None:
            diags.extend(
                check_page_table(
                    self.kv,
                    live_slots=set(self.scheduler.active),
                    program=f"{self.cfg.name}:page-table",
                )
            )
        if envelope is not None:
            plan = self.plan_capacity(envelope)
            diags.extend(
                plan.diagnostics(program=f"serve:{self.cfg.name}:capacity")
            )
        return diags

    def plan_capacity(self, envelope: Any = None) -> Any:
        """Static capacity plan of *this* deployment against a device
        envelope (default: probe the live device) — the serve-side
        analogue of the paper's FPGA resource-fit pre-check.  The plan's
        pool-token figure is cross-checked against the live ``PagePool``
        so the static math can never drift from the engine's accounting."""
        from repro.analysis.resources import plan_serve_capacity

        plan = plan_serve_capacity(
            self.cfg,
            n_slots=self.n_slots,
            max_len=self.max_len,
            page_size=self.kv.pool.page_size if self.kv is not None else None,
            n_pages=self.kv.pool.n_pages if self.kv is not None else None,
            envelope=envelope,
        )
        if self.kv is not None and plan.pool_tokens != self.kv.pool.token_capacity:
            raise AssertionError(
                f"capacity plan sized the pool at {plan.pool_tokens} tokens "
                f"but the live PagePool holds {self.kv.pool.token_capacity}"
            )
        return plan

    # -- phase execution -------------------------------------------------------
    def _padded_len(self, length: int) -> int:
        if self.prefill_bucket:
            bucket = self.prefill_bucket
            length = min(-(-length // bucket) * bucket, self.max_len)
        return length

    def _padded_prompt(self, context: Sequence[int]) -> np.ndarray:
        out = np.zeros((1, self._padded_len(len(context))), np.int32)
        out[0, : len(context)] = context
        return out

    def _request_knobs(self, state: RequestState) -> tuple[float, int]:
        return (state.request.sampling or self.sampler).knobs

    def _slot_page_row(self, slot: int) -> jax.Array:
        """The slot's (max_pages,) page-id operand for the insert program
        (null-page filled past the allocation)."""
        assert self.kv is not None
        return jnp.asarray(self.kv.array()[slot])

    def _preempt_for_pages(self, needy_slot: int) -> bool:
        """Reclaim pages by preempting the youngest other request —
        decoding victims first, then mid-prefill ones, finally the needy
        slot itself (requeue beats deadlock).  Returns False when there is
        nothing left to preempt."""
        decoding = [
            slot
            for slot in self.scheduler.active
            if slot not in self._prefilling and slot != needy_slot
        ]
        prefilling = [
            slot for slot in self._prefilling if slot != needy_slot
        ]
        pool = decoding or prefilling or (
            [needy_slot] if needy_slot in self.scheduler.active else []
        )
        if not pool:
            return False
        victim = max(pool, key=lambda s: self.scheduler.active[s].admit_seq)
        self._prefilling.pop(victim, None)
        self.scheduler.preempt(victim)
        self._gen_counts[victim] = 0
        self._lengths[victim] = 0
        return True

    def _ensure_pages(self, slot: int, n_tokens: int) -> None:
        """Grow the slot to ``n_tokens`` of page capacity, preempting under
        pool pressure.  Raises only when preemption cannot free enough —
        impossible for requests submit() admitted (each fits the pool
        alone)."""
        if self.kv is None:
            return
        while True:
            try:
                added = self.kv.ensure(slot, n_tokens)
                if added and self.tracer.enabled and (
                    slot in self.scheduler.active
                ):
                    state = self.scheduler.active[slot]
                    self.tracer.event(
                        "kv-grow", tid=request_track(state.request_id),
                        request=state.request_id, slot=slot,
                        pages=len(added),
                    )
                return
            except PoolExhausted:
                if not self._preempt_for_pages(slot):
                    raise
                if slot not in self.scheduler.active:
                    return  # the needy slot preempted itself: it no longer
                    # holds pages, and allocating onto a freed slot would
                    # leak them (callers re-check liveness)

    # -- chunked prefill -------------------------------------------------------
    def _plan_chunks(self, decoding: int) -> tuple[list[tuple[int, int]], int]:
        """Pick which mid-prefill slots run a chunk this step, and how many
        tokens each: budget-capped, but guaranteed progress when nothing
        else runs this step.  Returns ``(planned, reserved)`` — skipped
        chunks *reserve* their budget tokens so this step's admissions
        cannot refill the budget and starve an in-flight prefill forever."""
        budget = self.scheduler.max_tokens_per_step
        planned: list[tuple[int, int]] = []
        reserved = 0
        spent = decoding
        for slot in sorted(self._prefilling):
            prog = self._prefilling[slot]
            run = min(self.prefill_chunk, len(prog.context) - prog.pos)
            if budget is not None and spent + reserved + run > budget:
                if spent or planned:
                    reserved += run  # held against new admissions
                    continue  # decode / earlier chunks run first
                # nothing else runs this step: progress beats the budget
            planned.append((slot, run))
            spent += run
        return planned, reserved

    def _run_chunk(
        self, slot: int, run: int, events: list[Token | Completion]
    ) -> None:
        """Extend one request's working cache by one chunk; the final chunk
        samples the first token and commits the cache into the slot."""
        if slot not in self._prefilling:
            return  # preempted by an earlier slot's page-ensure this step
        prog = self._prefilling[slot]
        state = prog.state
        final = prog.pos + run >= len(prog.context)
        # pages for this chunk (reserved now, written at the final insert)
        self._ensure_pages(slot, prog.pos + run)
        if slot not in self._prefilling:
            return  # self-preempted under extreme pool pressure
        # the final chunk runs at its exact width: padding it to the chunk
        # would write zero-token K/V past the context end — and past the
        # cache end for a near-max_len prompt, where dynamic_update_slice
        # clamps the write *backward* over correct prompt rows.  One trace
        # per distinct tail length, same policy as the prefill program.
        tokens = np.asarray(
            [prog.context[prog.pos : prog.pos + run]], np.int32
        )
        self._chunk_calls += 1
        t0 = time.perf_counter()
        with self._phase("prefill"), meter_window(self.meter) as tele:
            if final:
                temp, topk = self._request_knobs(state)
                tok, b1_cache = self._extend_sample_fn(
                    self.params,
                    jnp.asarray(tokens),
                    prog.cache,
                    jnp.asarray(run - 1, jnp.int32),
                    jnp.asarray(state.seed, jnp.int32),
                    jnp.asarray(len(state.tokens), jnp.int32),
                    jnp.asarray(temp, jnp.float32),
                    jnp.asarray(topk, jnp.int32),
                )
                self._commit_slot(state, tok, b1_cache, events)
                del self._prefilling[slot]
            else:
                prog.cache = self._extend_fn(
                    self.params, jnp.asarray(tokens), prog.cache
                )
                prog.pos += run
        self.telemetry["prefill"].add(tele, run)
        if self.tracer.enabled:
            self.tracer.add_span(
                "prefill-chunk", t0, time.perf_counter(),
                tid=request_track(state.request_id),
                request=state.request_id, slot=slot, tokens=run,
                final=final, step=self._steps,
            )

    def _fresh_b1_cache(self) -> Any:
        return lm.init_cache(self.cfg, 1, self._slot_len)

    # -- admission / decode ----------------------------------------------------
    def _admit(self, state: RequestState) -> list[Token | Completion]:
        context = list(state.request.prompt) + list(state.tokens)
        if self._is_chunked(len(context)):
            self._prefilling[state.slot] = _PrefillProgress(
                state, context, self._fresh_b1_cache()
            )
            events: list[Token | Completion] = []
            with self.tracer.span("serve.chunk", slot=state.slot,
                                  tokens=self.prefill_chunk):
                self._run_chunk(state.slot, self.prefill_chunk, events)
            return events

        temp, topk = self._request_knobs(state)
        with self._phase("prefill"), meter_window(self.meter) as tele:
            with self.tracer.span("serve.prefill", tokens=len(context)):
                tokens = self._padded_prompt(context)
                tok, b1_cache = self._prefill_fn(
                    self.params,
                    jnp.asarray(tokens),
                    jnp.asarray(len(context) - 1, jnp.int32),
                    jnp.asarray(state.seed, jnp.int32),
                    jnp.asarray(len(state.tokens), jnp.int32),
                    jnp.asarray(temp, jnp.float32),
                    jnp.asarray(topk, jnp.int32),
                )
            events = []
            self._commit_slot(state, tok, b1_cache, events)
        self.telemetry["prefill"].add(tele, len(context))
        return events

    def _commit_slot(
        self,
        state: RequestState,
        tok: jax.Array,
        b1_cache: Any,
        events: list[Token | Completion],
    ) -> None:
        """Insert a fully prefilled batch-1 cache into the slot, record the
        sampled token and arm the slot for decode."""
        slot = state.slot
        context = self._ctx_len(state)
        with self.tracer.span("serve.insert", slot=slot):
            if self.paged:
                # pad the b1 cache's sequence up to whole pages so the
                # insert scatters complete pages (prefill already built
                # it that long)
                page_row = self._slot_page_row(slot)
            else:
                page_row = jnp.zeros((1,), jnp.int32)  # unused operand
            self.cache = self._insert_fn(
                self.cache, b1_cache, jnp.asarray(slot, jnp.int32), page_row
            )
        with self.tracer.span("serve.first_token"):
            first = int(np.asarray(tok)[0])  # blocks inside the meter window

        temp, topk = self._request_knobs(state)
        gen_index = len(state.tokens)
        self._last_tok[slot, 0] = first
        self._seeds[slot] = state.seed
        self._gen_counts[slot] = gen_index + 1
        self._temps[slot] = temp
        self._topks[slot] = topk
        # kv.lengths needs no sync: alloc_slot/ensure already tracked the
        # context through admission and the chunk loop
        self._lengths[slot] = context
        self._dev_index[slot] = context  # the inserted cache's index
        now = time.perf_counter()
        if self.tracer.enabled:
            track = request_track(state.request_id)
            # the prefill span covers admission -> first token, including
            # every chunk for chunked prompts (chunk sub-spans sit inside)
            self.tracer.add_span(
                "prefill", state.last_admitted_at or now, now, tid=track,
                request=state.request_id, slot=slot, tokens=context,
                step=self._steps,
            )
            if state.first_token_at is None:
                self.tracer.event(
                    "first-token", tid=track, request=state.request_id,
                    token=first,
                )
        if state.first_token_at is None:
            state.first_token_at = now
        state.tokens.append(first)
        events.append(
            Token(
                state.request_id, first, gen_index, "prefill", self._steps,
                now,
            )
        )
        if state.done:
            events.append(self._finish(slot))

    def _decode_active(self) -> list[Token | Completion]:
        span = self.tracer.span
        with span("serve.pages"):
            if self.paged:
                # grow page capacity for this step's writes up front; under
                # pool pressure this preempts the youngest request (which
                # may shrink the decoding set)
                for slot in sorted(self.scheduler.active):
                    if slot in self._prefilling:
                        continue
                    if slot not in self.scheduler.active:
                        continue  # preempted by an earlier slot's ensure
                    self._ensure_pages(slot, int(self._lengths[slot]) + 1)
            active = {
                slot: state
                for slot, state in self.scheduler.active.items()
                if slot not in self._prefilling
            }
            if not active:
                return []
            if self.kv is None:
                pages = jnp.zeros((1,), jnp.int32)  # unused operand
            else:
                if self._pages_version != self.kv.version:
                    self._pages_op = jnp.asarray(self.kv.array())
                    self._pages_version = self.kv.version
                pages = self._pages_op
                self._count_walk()
        t0 = time.perf_counter()
        self.monitor.start()
        with self._phase("decode"), meter_window(self.meter) as tele:
            with span("serve.decode", batch=len(active), step=self._steps):
                tok, self.cache = self._decode_fn(
                    self.params,
                    jnp.asarray(self._last_tok),
                    self.cache,
                    pages,
                    jnp.asarray(self._seeds),
                    jnp.asarray(self._gen_counts),
                    jnp.asarray(self._temps),
                    jnp.asarray(self._topks),
                )
            with span("serve.decode_wait"):
                # the only device->host transfer: (B,) token ids (and,
                # with MoE layers, the step's expert load behind them)
                toks = np.asarray(tok)
            at = time.perf_counter()
        if self._moe_pairs_c is not None:
            self._moe_pairs_c.inc(int(toks[self.n_slots]))
            self._moe_busiest_c.inc(int(toks[self.n_slots + 1]))
        self._dev_index += 1
        self.monitor.stop(self._steps)
        self.telemetry["decode"].add(tele, len(active))
        if self.tracer.enabled:
            # the fused step mirrored onto each participating request's
            # track, so per-request timelines show their decode cadence
            # (and the gaps where they waited)
            for state in active.values():
                self.tracer.add_span(
                    "decode", t0, at, tid=request_track(state.request_id),
                    request=state.request_id, step=self._steps,
                )

        events: list[Token | Completion] = []
        with span("serve.emit"):
            for slot, state in active.items():
                token = int(toks[slot])
                self._last_tok[slot, 0] = token
                self._gen_counts[slot] += 1
                # kv.lengths needs no sync: _ensure_pages set it to this
                # very value before the step ran
                self._lengths[slot] += 1
                index = len(state.tokens)
                state.tokens.append(token)
                events.append(
                    Token(
                        state.request_id, token, index, "decode",
                        self._steps, at,
                    )
                )
                if state.done:
                    events.append(self._finish(slot))
        return events

    def _count_walk(self) -> None:
        """Count this decode step's page-walk trips, computed on the host
        from the mirrors of the kernel's operands by the kernel's own
        :func:`walk_blocks`, beside the trips a walk over all
        ``max_pages`` would make."""
        assert self.kv is not None
        ps = self.kv.pool.page_size
        for layers, block in self._walks:
            walked = walk_blocks(
                self._dev_index, self.kv.array(), 1, page_size=ps,
                block=block, null_page=self.kv.pool.null_page,
            )
            self._walk_c.inc(layers * int(walked.max()))
            self._walk_full_c.inc(
                layers * -(-self.kv.max_pages * ps // block)
            )

    def _finish(self, slot: int) -> Completion:
        state = self.scheduler.release(slot)
        self._gen_counts[slot] = 0
        self._lengths[slot] = 0
        completion = Completion(
            request_id=state.request_id,
            prompt=state.request.prompt,
            tokens=tuple(state.tokens),
            finish_reason=state.finish_reason,
            submitted_at=state.submitted_at,
            first_token_at=state.first_token_at or time.perf_counter(),
            finished_at=time.perf_counter(),
            admitted_at=state.admitted_at,
        )
        self._completed_c.inc()
        self._generated_c.inc(len(completion.tokens))
        if self.tracer.enabled:
            self.tracer.event(
                "complete", tid=request_track(state.request_id),
                request=state.request_id, tokens=len(completion.tokens),
                reason=completion.finish_reason,
            )
        self.completions[state.request_id] = completion
        self._finished.append(completion)
        return completion
