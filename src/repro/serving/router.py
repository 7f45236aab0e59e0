"""RouterService: the end-to-end serving pipeline.

    DSL text ──parse/compile──► RouterConfig ──validate──► diagnostics
         │                                              (errors abort)
         └──bind──► SignalEngine (embedder + centroids)
                          │
    requests ──embed──► signal scores ──group norm──► activations
                          │
                  tensorized policy eval (serving/policy.py)
                          │
                  Batcher ──► backend models (models/) decode loop

Routing is one fused, jit-cached program: embeddings and crisp scores
enter ``_route_core`` (signal GEMM + grouped Voronoi normalization +
thresholds/default fallback + policy argmax) and route *indices* come
out — ``route``, ``route_actions`` and ``submit`` all derive their
strings from that single evaluation, so a ``submit`` batch embeds and
scores exactly once.  With ``kernel="fused"`` (the TPU default) the
whole signal layer additionally collapses into the single
centroid-resident ``fused_route`` Pallas launch (auto-upgrading to the
D-tiled streaming variant past the VMEM budget), and with ``mesh=``
bound it routes through the shard_map lowering — batch over the data
axes, centroid columns over model, exact cross-device winner
reductions.  ``precision=`` selects the bf16/int8 centroid store.  The
jitted callable and the device-resident ``PolicyTables`` are cached on
the service across request batches.

Serving runs in two modes: the one-shot ``submit``/``step``/``drain``
path (FIFO ``Batcher``), and the continuous-batching loop —
``enqueue`` admits requests with optional SLO deadlines into
per-backend admission queues (duplicate in-flight texts coalesce onto
one decode slot), ``serve_step`` releases the most urgent ready batch
(full / waited-too-long / deadline-imminent) into the decode loop, and
``serve_forever`` drives steps until idle.  With ``slots=N`` the
continuous loop decodes through the preemptible slot scheduler
(serving/scheduler.py): one pooled decode step at a time, admission
between steps, immediate slot retirement, and deadline-driven
preemption — instead of the whole-batch fallback that decodes each
released batch to completion.

The fault-tolerant tier wraps all of the above:

* **Policy generations + hot-swap** — the bound policy lives in a
  refcounted ``PolicyGeneration``; ``rebind(dsl_text)`` compiles,
  validates, and binds a replacement, runs the paper's detection
  hierarchy (SAT + spherical-cap taxonomy) as an *admission gate* — a
  policy that fails compile/validate or introduces a new T4 probable
  conflict is rejected with the old generation untouched — then
  atomically flips new arrivals to generation N+1 while in-flight
  requests finish on N; a retired generation is freed once its
  refcount drains.
* **Failure containment** — every backend decode is guarded by
  ``serving/faults.py``: fault injection for chaos tests, per-request
  retry with jittered exponential backoff, a per-backend circuit
  breaker, and graceful degradation to the policy's default backend
  when a breaker opens.  A failed batch marks only its own requests
  ``failed`` (with the error recorded); the serve loop never dies.
* **Audit trail** — with ``audit=`` enabled, every routing decision,
  terminal request, fault, re-route, breaker transition, and rebind
  appends a structured record to a bounded ring/JSONL sink
  (serving/audit.py), and each generation's ``OnlineConflictMonitor``
  watches the live score stream for co-fire/against-evidence drift
  (``conflict_alerts()``).

Backends are real JAX models: the reduced same-family config by
default, the published config when a BACKEND block says
``size: "published"`` (docs/dsl.md).
"""
from __future__ import annotations

import dataclasses
import functools
import time
import zlib
from typing import Any, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.registry import get_config
from repro.core.monitor import OnlineConflictMonitor
from repro.analysis.engine import PolicySummary, WholePolicyAnalyzer
from repro.core.taxonomy import (Finding, blocking_findings, finding_key)
from repro.dsl.compiler import CompileError, RouterConfig, compile_text
from repro.dsl.validate import Diagnostic, Validator, has_errors
from repro.models.model import build_model
from repro.serving import policy as policy_mod
from repro.serving.audit import AuditSink, qhash
from repro.serving.batcher import (Batcher, ContinuousBatcher, Request,
                                   finish_request)
from repro.serving.brownout import BrownoutConfig, BrownoutController
from repro.serving.faults import (BreakerConfig, FaultManager, RetryPolicy)
from repro.serving.spans import PhaseCounters
from repro.signals import engine as engine_mod
from repro.signals.embedder import HashEmbedder


@functools.partial(jax.jit,
                   static_argnames=("n_rules", "kernel_mode", "interpret",
                                    "nprobe", "block_b"))
def _route_core(emb, crisp_raw, tensors, jt, n_rules, kernel_mode,
                interpret, nprobe=1, block_b=128):
    """embeddings + crisp scores -> (route index, score, normalized
    activations, fired mask): the whole signal pipeline and the policy
    argmax as one XLA program.  ``kernel_mode`` picks the signal
    lowering (jnp / grouped Pallas / the fully-fused centroid-resident
    fused_route kernel / the two-stage ``ivf``/``ivf_fused`` path,
    probing ``nprobe`` coarse clusters).  The activation outputs feed
    the online conflict monitor and the audit trail; callers that
    ignore them pay nothing (they are intermediates of the fused
    program either way, and stay on device unless materialized)."""
    _, normalized, fired, conf = engine_mod._signal_eval_core(
        emb, crisp_raw, tensors, kernel_mode=kernel_mode,
        interpret=interpret, nprobe=nprobe, block_b=block_b)
    idx, score = policy_mod.evaluate_policy(jt, n_rules, fired, conf)
    return idx, score, normalized, fired


@functools.lru_cache(maxsize=16)
def _sharded_route_core(mesh, n_rules: int, body_kernel: str = "jnp",
                        interpret: bool = False):
    """Mesh twin of ``_route_core``: the shard_map'd signal layer and
    the policy argmax compose into one jitted program per (mesh,
    n_rules) — no host-visible (B, N) intermediates between them.
    This is the *observing* path: it materializes the full normalized /
    fired matrices for the conflict monitor and audit trail.  When
    nothing observes, the router takes
    ``distributed/policy_shard.sharded_route_policy`` instead, which
    psum_scatters the policy argmax and never replicates fired/conf."""
    eval_fn = engine_mod._sharded_signal_eval(mesh, body_kernel,
                                              interpret)

    @jax.jit
    def fn(emb, crisp_raw, st, jt):
        _, normalized, fired, conf = eval_fn(emb, crisp_raw, st)
        idx, score = policy_mod.evaluate_policy(jt, n_rules, fired, conf)
        return idx, score, normalized, fired

    return fn


BACKEND_SIZES = ("smoke", "published")


@functools.lru_cache(maxsize=16)
def _init_params(cfg):
    """Jitted ``Model.init`` for one config: weights are drawn and cast
    inside one program, so a bf16 backend never holds its float32 image
    on the device, and services loading the same config share the
    compile."""
    return jax.jit(build_model(cfg).init)


@dataclasses.dataclass
class BackendRuntime:
    """One loaded backend model: params plus its jitted prefill /
    decode-step callables and the KV budget (``max_seq``)."""

    name: str
    arch: str
    model: Any
    params: Any
    decode: Any                    # jitted decode_step
    prefill: Any                   # jitted prefill
    max_seq: int = 128


@dataclasses.dataclass
class PolicyGeneration:
    """One bound policy version: everything routing needs, refcounted.

    ``inflight`` counts admitted-but-not-terminal requests stamped with
    this generation; a retired generation is freed (dropped from the
    service's table, device tables garbage-collected) once it drains.
    ``blocking_keys`` caches the identity set of this generation's
    blocking taxonomy findings so the admission gate can tell *new*
    conflicts from pre-existing ones."""
    gen_id: int
    config: RouterConfig
    engine: Any                    # SignalEngine
    tables: policy_mod.PolicyTables
    jt: Dict[str, jnp.ndarray]
    diagnostics: List[Diagnostic]
    fingerprint: str
    monitor: Optional[OnlineConflictMonitor] = None
    inflight: int = 0
    retired: bool = False
    blocking_keys: Optional[frozenset] = None
    # cached whole-policy analysis summary (analysis/engine.py) — the
    # base the next rebind's delta pass re-analyzes against
    analysis: Optional[PolicySummary] = None
    # rule-aligned sharded term tables (distributed/policy_shard) —
    # built only when the engine's shard_map path is active, so the
    # non-observing mesh route can psum_scatter the policy argmax
    pshard: Optional[Dict[str, jnp.ndarray]] = None
    # seconds per build phase (``router.build.*``) of this generation
    build_s: Dict[str, float] = dataclasses.field(default_factory=dict)


@dataclasses.dataclass
class RebindResult:
    """Outcome of a hot-swap attempt.  ``generation`` is the generation
    actually serving after the call — the new one on accept, the old
    (uninterrupted) one on reject."""
    accepted: bool
    generation: int
    reasons: List[str] = dataclasses.field(default_factory=list)
    diagnostics: List[Diagnostic] = dataclasses.field(default_factory=list)
    blocking: List[Finding] = dataclasses.field(default_factory=list)
    # analyzer work counters from the admission gate (delta pass on the
    # common path) — AnalysisCounters.as_dict(), None if taxonomy skipped
    analysis: Optional[dict] = None


class RouterService:
    """The end-to-end serving pipeline for one DSL policy.

    Compiles/validates/binds ``dsl_text`` into generation 0, loads the
    policy's backends (real JAX models), and serves through either the
    one-shot ``submit``/``step``/``drain`` path or the continuous
    ``enqueue``/``serve_step`` loop (whole-batch, or the preemptible
    slot scheduler with ``slots=N``).  See the module docstring for the
    full dataflow; docs/architecture.md for the layer map.
    """

    def __init__(self, dsl_text: str, *, embedder=None,
                 load_backends: bool = True, max_batch: int = 8,
                 use_pallas_voronoi: bool = False,
                 kernel: Optional[str] = None,
                 precision: Optional[str] = None,
                 mesh=None,
                 two_stage: Optional[bool] = None,
                 nprobe: Optional[int] = None,
                 body_kernel: Optional[str] = None,
                 slots: Optional[int] = None,
                 max_slots: Optional[int] = None, preempt: bool = True,
                 validate: bool = True, run_taxonomy: bool = False,
                 audit=None, monitor: Optional[bool] = None,
                 retry: Optional[RetryPolicy] = None,
                 breaker: Optional[BreakerConfig] = None,
                 fault_seed: int = 0,
                 queue_cap: Optional[int] = None,
                 brownout=None,
                 prefill_chunk: Optional[int] = None):
        """Args:
            dsl_text: Semantic Router DSL source (docs/dsl.md).
            embedder: signal embedder (default ``HashEmbedder``).
            load_backends: load the policy's declared backend models.
            max_batch: batch cap for both batchers.
            use_pallas_voronoi: legacy alias for ``kernel="grouped"``.
            kernel: signal lowering — ``"jnp"``, ``"grouped"``, or the
                fully fused ``"fused"`` Pallas launch.
            precision: centroid store precision (``"bf16"``/``"int8"``/
                packed ``"int4"``).
            mesh: JAX mesh for the sharded routing lowering.
            two_stage: force the two-stage IVF routing path on/off
                (default: auto by route-table size — see
                ``SignalEngine``).
            nprobe: coarse clusters probed per query on the two-stage
                path (default: recall-tuned ~sqrt(n_slabs)).
            body_kernel: per-device lowering inside the shard_map body
                (``"pallas"`` runs the fused similarity kernel inside
                the mesh program; default auto by backend).
            slots: ``N`` switches continuous serving to the preemptible
                slot scheduler with N slots per backend; ``None`` keeps
                whole-batch decode.
            max_slots: autoscale ceiling for the slot scheduler (pooled
                KV rows are sized for it up front; see
                ``DecodeScheduler.set_slots``).
            preempt: enable deadline-driven preemption in slot mode.
            validate: run static validation (errors raise).
            run_taxonomy: include the geometric taxonomy in validation.
            audit: ``AuditSink`` | True (in-memory ring) | None/False.
            monitor: online conflict monitor on/off (defaults to follow
                ``audit``).
            retry: backend retry policy (default ``RetryPolicy()``).
            breaker: circuit-breaker config (default ``BreakerConfig()``).
            fault_seed: RNG seed for fault injection/backoff jitter.
            queue_cap: bound on each backend's admission queue —
                ``enqueue`` sheds (terminal, with ``shed_reason``)
                instead of queueing past it; ``None`` = unbounded (the
                pre-ingress behavior).
            brownout: ``BrownoutConfig`` | True (defaults) | None/False
                — the graceful-degradation ladder
                (serving/brownout.py).  Enabling it without
                ``queue_cap`` applies a default cap of 64 (the ladder
                needs a pressure scale).
            prefill_chunk: slot-mode chunked prefill — long prompts
                prefill ``prefill_chunk`` tokens per pooled step
                instead of one whole-prompt shot (``None`` = single-
                shot; requires the backend model to support chunked
                prefill, else that backend falls back to single-shot).

        Raises:
            ValueError: when validation finds errors in ``dsl_text``.
        """
        self.embedder = embedder or HashEmbedder()
        self._engine_opts = dict(use_pallas=use_pallas_voronoi,
                                 kernel=kernel, precision=precision,
                                 mesh=mesh, two_stage=two_stage,
                                 nprobe=nprobe, body_kernel=body_kernel)
        self._validate = validate
        self._run_taxonomy = run_taxonomy
        self._load_backends_flag = load_backends
        self.batcher = Batcher(max_batch=max_batch)
        self.cbatcher = ContinuousBatcher(max_batch=max_batch)
        # audit: AuditSink instance | True (default in-memory ring) |
        # None/False (disabled — zero serving-path overhead).  The
        # sink's clock chains through the batcher's so fake-clock tests
        # stamp audit records consistently.
        if isinstance(audit, AuditSink):
            self.audit: Optional[AuditSink] = audit
        elif audit:
            self.audit = AuditSink(clock=lambda: self.cbatcher.clock())
        else:
            self.audit = None
        # monitor default follows audit: observability on or off as one
        self._monitor_enabled = bool(audit) if monitor is None \
            else bool(monitor)
        # route-call phases (serving/spans.py) and the rows they routed
        self._route_phases = PhaseCounters()
        self._route_rows = 0
        self._route_bucket_rows = 0
        self.faults = FaultManager(
            retry=retry, breaker=breaker,
            clock=lambda: self.cbatcher.clock(), seed=fault_seed,
            on_transition=self._audit_breaker)
        # ---- generation 0 ----------------------------------------------------
        self._gen_counter = 0
        gen = self._build_generation(dsl_text, gen_id=0,
                                     validate=validate,
                                     run_taxonomy=run_taxonomy)
        self._gens: Dict[int, PolicyGeneration] = {0: gen}
        self._gen = gen
        self.backends: Dict[str, BackendRuntime] = {}
        if load_backends:
            self._load_backends(gen.config)
        # slots=N switches the continuous loop from whole-batch decode to
        # the preemptible slot scheduler (serving/scheduler.py); slots=
        # None keeps the whole-batch fallback
        self.scheduler = None
        if slots is not None:
            from repro.serving.scheduler import DecodeScheduler
            self.scheduler = DecodeScheduler(
                self.backends, self.cbatcher, n_slots=slots,
                max_slots=max_slots, preempt=preempt, faults=self.faults,
                fallback=self._fallback_for,
                on_done=self._on_request_done, audit=self.audit,
                prefill_chunk=prefill_chunk)
        # ---- overload control ------------------------------------------------
        self.queue_cap = queue_cap
        self.overload = {"accepted": 0, "shed": 0, "timed_out": 0,
                         "cancelled": 0}
        self.brownout: Optional[BrownoutController] = None
        if brownout:
            bcfg = brownout if isinstance(brownout, BrownoutConfig) \
                else BrownoutConfig()
            if self.queue_cap is None:
                self.queue_cap = 64
            self.brownout = BrownoutController(self, bcfg)

    # ---- generation plumbing (back-compat views) ------------------------------
    @property
    def config(self) -> RouterConfig:
        """The serving generation's compiled ``RouterConfig``."""
        return self._gen.config

    @property
    def engine(self):
        """The serving generation's bound ``SignalEngine``."""
        return self._gen.engine

    @property
    def tables(self) -> policy_mod.PolicyTables:
        """The serving generation's tensorized policy tables."""
        return self._gen.tables

    @property
    def _jt(self):
        return self._gen.jt

    @property
    def diagnostics(self) -> List[Diagnostic]:
        """Validation diagnostics from the serving generation's bind."""
        return self._gen.diagnostics

    @property
    def generation(self) -> int:
        """The generation id new arrivals are stamped with."""
        return self._gen.gen_id

    def generations(self) -> Dict[int, Dict[str, Any]]:
        """Live generation table: {gen_id: {inflight, retired}}."""
        return {g.gen_id: {"inflight": g.inflight, "retired": g.retired}
                for g in self._gens.values()}

    def _build_generation(self, dsl_text: str, gen_id: int,
                          validate: bool = True,
                          run_taxonomy: bool = False) -> PolicyGeneration:
        from repro.signals.engine import SignalEngine
        build = PhaseCounters()
        with build.phase("router.build.compile"):
            config = compile_text(dsl_text)
        diagnostics: List[Diagnostic] = []
        if validate:
            with build.phase("router.build.validate"):
                diagnostics = Validator(config).validate(
                    run_taxonomy=run_taxonomy)
            if has_errors(diagnostics):
                msgs = "\n".join(str(d) for d in diagnostics
                                 if d.severity == "error")
                raise ValueError(f"config has validation errors:\n{msgs}")
        with build.phase("router.build.engine"):
            engine = SignalEngine(config, self.embedder, **self._engine_opts)
        with build.phase("router.build.tables"):
            tables = policy_mod.build_tables(config)
        mon = None
        if self._monitor_enabled:
            mon = OnlineConflictMonitor(
                engine.names, priority_of=self._atom_priorities(config))
        with build.phase("router.build.upload"):
            jt = tables.as_jax()
            pshard = None
            if engine.sharded_active:
                from repro.distributed import policy_shard as pshard_mod
                pshard = {
                    k: jnp.asarray(v)
                    for k, v in pshard_mod.build_policy_shard_tables(
                        tables,
                        prob_cols=np.asarray(engine.tensors["prob_cols"]),
                        crisp_cols=np.asarray(engine.tensors["crisp_cols"]),
                        n_model=engine_mod.mesh_model_size(
                            engine.mesh)).items()}
            # the span times the copies, not their enqueue
            jax.block_until_ready((jt, pshard))
        return PolicyGeneration(
            gen_id=gen_id, config=config, engine=engine, tables=tables,
            jt=jt, diagnostics=diagnostics,
            fingerprint=config.fingerprint(), monitor=mon,
            pshard=pshard,
            build_s={k: v["seconds"]
                     for k, v in build.snapshot().items()})

    @staticmethod
    def _atom_priorities(config: RouterConfig) -> Dict[str, int]:
        """Per-signal priority for the online monitor's against-evidence
        direction: the highest priority among rules referencing it."""
        pr: Dict[str, int] = {}
        for r in config.rules:
            for a in r.condition.atoms():
                pr[a] = max(pr.get(a, r.priority), r.priority)
        return pr

    def _analyzer(self, gen: PolicyGeneration) -> WholePolicyAnalyzer:
        return WholePolicyAnalyzer(gen.config.signals,
                                   gen.config.exclusive_groups(),
                                   fingerprint=gen.fingerprint)

    def _policy_summary(self, gen: PolicyGeneration) -> PolicySummary:
        """``gen``'s whole-policy analysis summary, computed once and
        cached.  Computed post-bind (its engine already wrote live
        centroids back into the atoms), so old and new generations
        compare on the same geometry; the summary's per-rule context
        hashes are what the next rebind's delta pass diffs against."""
        if gen.analysis is None:
            result = self._analyzer(gen).analyze(gen.config.rules)
            gen.analysis = result.summary
            gen.blocking_keys = frozenset(
                finding_key(f)
                for f in blocking_findings(result.findings))
        return gen.analysis

    def _blocking_keys(self, gen: PolicyGeneration) -> frozenset:
        """Identity set of ``gen``'s blocking taxonomy findings, cached."""
        if gen.blocking_keys is None:
            self._policy_summary(gen)
        return gen.blocking_keys

    # ---- hot-swap --------------------------------------------------------------
    def rebind(self, dsl_text: str, *,
               run_taxonomy: bool = True) -> RebindResult:
        """Zero-downtime policy hot-swap with a conflict admission gate.

        Compiles and binds ``dsl_text`` beside the serving generation
        (device tables are memoized per content/mesh/precision, so a
        re-bind of known content is cheap), then gates admission on the
        paper's detection hierarchy: compile errors, validation errors,
        and any *newly introduced* blocking finding (a T4 probable
        conflict, or any error-severity finding, not already present in
        the serving generation) reject the swap — the old generation
        keeps serving, untouched.  On accept, new arrivals flip
        atomically to generation N+1; in-flight requests finish on N,
        and N is freed once its refcount drains."""
        old = self._gen

        def reject(reasons, diags=(), blocking=()):
            if self.audit:
                self.audit.log("rebind", generation=old.gen_id,
                               failed=True,
                               detail={"reasons": list(reasons)})
            return RebindResult(False, old.gen_id, list(reasons),
                                list(diags), list(blocking))

        # 1. compile (ParseError is a SyntaxError, not a CompileError)
        try:
            config = compile_text(dsl_text)
        except (CompileError, SyntaxError) as e:
            return reject([f"compile error: {e}"])
        if config.fingerprint() == old.fingerprint and not old.retired:
            if self.audit:
                self.audit.log("rebind", generation=old.gen_id,
                               detail={"noop": True})
            return RebindResult(True, old.gen_id,
                                ["no-op: identical policy source"])
        # 2. validate (static checks; the geometric taxonomy runs
        #    post-bind below, on live centroids)
        diags = Validator(config).validate(run_taxonomy=False)
        if has_errors(diags):
            return reject(
                [str(d) for d in diags if d.severity == "error"], diags)
        # 3. bind: builds the engine (embedder + live centroids written
        #    back into the atoms) + policy tables, old gen still serving
        try:
            gen = self._build_generation(dsl_text,
                                         gen_id=self._gen_counter + 1,
                                         validate=False)
        except Exception as e:  # noqa: BLE001 — bind must not kill serving
            return reject([f"bind error: {type(e).__name__}: {e}"], diags)
        gen.diagnostics = diags
        # 4. admission gate: the detection hierarchy on the bound
        #    policy, as a *delta* pass against the serving generation's
        #    cached summary — only rules whose context (condition,
        #    priority, signal geometry, group membership) changed are
        #    re-analyzed, O(changed) instead of O(N²); block on
        #    conflicts the swap would *introduce*
        counters = None
        if run_taxonomy:
            result = self._analyzer(gen).analyze(
                gen.config.rules, base=self._policy_summary(old))
            counters = result.counters.as_dict()
            gen.analysis = result.summary
            blocking = blocking_findings(result.findings)
            gen.blocking_keys = frozenset(finding_key(f) for f in blocking)
            introduced = [f for f in blocking
                          if finding_key(f) not in self._blocking_keys(old)]
            if introduced:
                rej = reject(
                    [f"{f.kind.name} {f.rules}: {f.detail}"
                     for f in introduced], diags, introduced)
                rej.analysis = counters
                return rej
        # 5. backends the new policy needs that are not loaded yet
        if self._load_backends_flag:
            self._load_backends(gen.config)
        # 6. atomic flip: one reference assignment — new arrivals route
        #    on N+1 from the next enqueue/submit; in-flight finish on N
        self._gen_counter = gen.gen_id
        self._gens[gen.gen_id] = gen
        old.retired = True
        self._gen = gen
        self._free_if_drained(old)
        if self.audit:
            self.audit.log("rebind", generation=gen.gen_id,
                           detail={"from": old.gen_id,
                                   "fingerprint": gen.fingerprint})
        return RebindResult(True, gen.gen_id, analysis=counters)

    def _free_if_drained(self, gen: PolicyGeneration) -> None:
        if gen.retired and gen.inflight <= 0 and gen is not self._gen:
            self._gens.pop(gen.gen_id, None)

    def _on_request_done(self, req: Request) -> None:
        """Terminal hook for every request (leaders and coalesced
        followers alike): drop the generation refcount, free drained
        retired generations, and append the ``serve`` audit record."""
        gen = self._gens.get(req.generation)
        if gen is not None:
            gen.inflight -= 1
            if gen.retired:
                self._free_if_drained(gen)
        if req.cancelled:
            self.overload["cancelled"] += 1
        elif req.timed_out:
            self.overload["timed_out"] += 1
        if self.audit:
            lat = (req.finish_s - req.arrival_s
                   if req.finish_s is not None and req.arrival_s is not None
                   else None)
            self.audit.log(
                "serve", generation=req.generation,
                query_hash=qhash(req.text), route=req.route,
                backend=req.backend, retries=req.retries,
                fallback_used=req.fallback_used, failed=req.failed,
                detail={"error": req.error, "latency_s": lat,
                        "tokens": len(req.output_tokens),
                        "truncated": req.truncated,
                        "coalesced": req.coalesced,
                        "cancelled": req.cancelled,
                        "timed_out": req.timed_out})

    def _audit_breaker(self, backend: str, state: str) -> None:
        if self.audit:
            self.audit.log("breaker", backend=backend,
                           detail={"state": state})

    # ---- backends -------------------------------------------------------------
    def _load_backends(self, config: Optional[RouterConfig] = None):
        """Load every backend ``config`` declares that is not already
        resident (rebind reuses loaded models across generations).

        A BACKEND's ``size`` picks its widths: ``"smoke"`` (default) is
        the reduced same-family variant, ``"published"`` the arch's own
        config (configs/archs.py, bf16)."""
        config = config if config is not None else self.config
        for name, fields in config.backends.items():
            if name in self.backends:
                continue
            arch = str(fields.get("arch", "internlm2-1.8b"))
            size = str(fields.get("size", "smoke"))
            if size not in BACKEND_SIZES:
                raise ValueError(f"BACKEND {name}: size must be one of "
                                 f"{BACKEND_SIZES}, got {size!r}")
            cfg = get_config(arch, smoke=size == "smoke")
            model = build_model(cfg)
            max_seq = int(fields.get("max_seq", 128))
            # stable digest, NOT hash(): Python string hashing is salted
            # per process, so hash(name) weights differ across runs and
            # decode tokens are irreproducible
            seed = zlib.crc32(name.encode("utf-8")) & 0xFFFF
            params = _init_params(cfg)(jax.random.PRNGKey(seed))
            self.backends[name] = BackendRuntime(
                name=name, arch=arch, model=model, params=params,
                decode=jax.jit(model.decode_step,
                               static_argnames=()),
                prefill=jax.jit(functools.partial(model.prefill,
                                                  max_seq=max_seq)),
                max_seq=max_seq)

    def _fallback_for(self, backend: str,
                      gen: Optional[PolicyGeneration] = None
                      ) -> Optional[str]:
        """The degradation target when ``backend`` is failing: the
        policy's default model, if it is loaded, distinct, and its own
        breaker is not open."""
        gen = gen or self._gen
        da = gen.config.default_action
        if da is None:
            return None
        fb = da.target
        if fb == backend or fb not in self.backends:
            return None
        if self.faults.is_open(fb):
            return None
        return fb

    # ---- routing ---------------------------------------------------------------
    def _route_eval(self, texts: Sequence[str],
                    metadata: Optional[Sequence[Dict[str, Any]]] = None,
                    gen: Optional[PolicyGeneration] = None):
        """-> (route idx, score) per request from ONE evaluation of the
        fused signal+policy program, feeding the activation stream to
        the generation's conflict monitor and the audit trail when
        either is enabled.

        Batches are padded up to the next power-of-two bucket so the
        jit cache compiles one variant per power of two up to the
        largest batch seen (instead of one per distinct batch size).

        Each call runs as the ``router.route`` phase, and its steps as
        ``router.embed``, ``router.pack`` (crisp scores, padding, the
        host-to-device copies), ``router.dispatch`` (the program's
        asynchronous launch), ``router.fetch`` (the wait for and copy
        of the results) and ``router.observe`` (monitor and audit):
        profiler spans and counters, ``telemetry()["route"]``."""
        gen = gen or self._gen
        if not texts:
            # (b-1).bit_length() on b == 0 would pad a phantom row and
            # compile a 1-row variant just to slice it away again
            return np.zeros((0,), np.int64), np.zeros((0,), np.float32)
        observe = gen.monitor is not None or self.audit is not None
        engine = gen.engine
        ph = self._route_phases
        b = len(texts)
        bucket = b
        if engine.fused_ok:
            bucket = 1 << max(0, (b - 1).bit_length())
            if engine.sharded_active:
                # keep buckets divisible by the mesh's data axes so the
                # batch shards instead of replicating
                dsz = engine_mod.mesh_data_size(engine.mesh)
                bucket += (-bucket) % dsz
        self._route_rows += b
        self._route_bucket_rows += bucket
        with ph.phase("router.route", rows=b, bucket=bucket,
                      call=ph.count("router.route")):
            if not engine.fused_ok:
                res = engine.evaluate(texts, metadata)
                idx, score = policy_mod.evaluate_indices(
                    gen.tables, res.fired, res.confidence)
                if observe:
                    self._observe(gen, texts, idx, score, res.normalized,
                                  res.fired)
                return idx, score
            with ph.phase("router.embed"):
                emb = engine.embed(texts)
            with ph.phase("router.pack"):
                crisp = engine.crisp_scores(texts, metadata)
                if bucket != b:
                    pad = ((0, bucket - b), (0, 0))
                    # an on-device embedder's rows stay on the device
                    emb = (jnp.pad if isinstance(emb, jax.Array)
                           else np.pad)(emb, pad)
                    crisp = np.pad(crisp, pad)
                emb, crisp = jnp.asarray(emb), jnp.asarray(crisp)
            with ph.phase("router.dispatch"):
                if engine.sharded_active and not observe \
                        and gen.pshard is not None:
                    # nothing observes: psum_scatter the policy argmax —
                    # fired/conf stay sharded, only (B,) vectors cross
                    # the mesh (distributed/policy_shard)
                    from repro.distributed import policy_shard as pshard_mod
                    idx, score = pshard_mod.sharded_route_policy(
                        engine.mesh, gen.tables.n_rules,
                        engine.body_kernel, engine.interpret)(
                        emb, crisp, engine.sharded_tensors, gen.pshard)
                elif engine.sharded_active:
                    idx, score, norm, fired = _sharded_route_core(
                        engine.mesh, gen.tables.n_rules,
                        engine.body_kernel, engine.interpret)(
                        emb, crisp, engine.sharded_tensors, gen.jt)
                else:
                    idx, score, norm, fired = _route_core(
                        emb, crisp, engine.tensors, gen.jt,
                        gen.tables.n_rules, kernel_mode=engine.kernel_mode,
                        interpret=engine.interpret, nprobe=engine.nprobe,
                        block_b=engine.block_b)
            with ph.phase("router.fetch"):
                idx = np.asarray(idx)[:b]
                score = np.asarray(score)[:b]
            if observe:
                with ph.phase("router.observe"):
                    self._observe(gen, texts, idx, score,
                                  np.asarray(norm)[:b],
                                  np.asarray(fired)[:b])
            return idx, score

    def _observe(self, gen: PolicyGeneration, texts, idx, score,
                 normalized, fired) -> None:
        if gen.monitor is not None:
            gen.monitor.observe_batch(np.asarray(normalized),
                                      gen.engine.effective_thresholds)
        if self.audit is not None:
            names = gen.engine.names
            fired = np.asarray(fired, bool)
            for k, text in enumerate(texts):
                s = float(score[k])
                self.audit.log(
                    "route", generation=gen.gen_id,
                    query_hash=qhash(text),
                    route=gen.tables.rule_name(int(idx[k])),
                    fired=tuple(names[j]
                                for j in np.flatnonzero(fired[k])),
                    margin=s if np.isfinite(s) else 0.0)

    def route_indices(self, texts: Sequence[str],
                      metadata: Optional[Sequence[Dict[str, Any]]] = None
                      ) -> np.ndarray:
        """-> winning route index per request (n_rules == default)."""
        idx, _ = self._route_eval(texts, metadata)
        return idx

    def route(self, texts: Sequence[str],
              metadata: Optional[Sequence[Dict[str, Any]]] = None
              ) -> List[str]:
        """-> winning route name per request."""
        return [self.tables.rule_name(i)
                for i in self.route_indices(texts, metadata)]

    def route_actions(self, texts: Sequence[str], metadata=None) -> List[str]:
        """-> winning action key (``model:NAME``/``plugin:NAME``/...)
        per request."""
        return [self.tables.action_key(i)
                for i in self.route_indices(texts, metadata)]

    def run_test_blocks(self) -> List[Diagnostic]:
        """The M4 empirical half: TEST assertions via the live pipeline."""
        return Validator(self.config).run_tests(
            lambda q: self.route([q])[0])

    def conflict_alerts(self, min_obs: int = 100) -> List[Finding]:
        """The serving generation's online-monitor findings (T5/T6 drift
        under the live distribution), mirrored into the audit sink."""
        gen = self._gen
        if gen.monitor is None:
            return []
        alerts = gen.monitor.alerts(min_obs=min_obs)
        if self.audit:
            for f in alerts:
                self.audit.log(
                    "conflict_alert", generation=gen.gen_id,
                    detail={"kind": f.kind.name, "rules": list(f.rules),
                            "evidence": dict(f.evidence or {}),
                            "detail": f.detail})
        return alerts

    # ---- serving ---------------------------------------------------------------
    def submit(self, texts: Sequence[str], metadata=None,
               max_new_tokens: int = 8) -> List[Request]:
        """Route a batch and queue model-bound requests (one-shot path).

        Args:
            texts: prompts to route.
            metadata: optional per-request metadata dicts.
            max_new_tokens: decode budget per request.

        Returns:
            One ``Request`` per text; plugin/reject actions come back
            already terminal, model-bound requests decode via
            ``step``/``drain``.
        """
        metadata = metadata or [None] * len(texts)
        # evaluate the signal pipeline ONCE; actions and route names are
        # two string views of the same winning indices
        gen = self._gen
        indices, _ = self._route_eval(texts, metadata, gen=gen)
        actions = [gen.tables.action_key(i) for i in indices]
        names = [gen.tables.rule_name(i) for i in indices]
        reqs = []
        for text, meta, action, rname in zip(texts, metadata, actions, names):
            kind, _, target = action.partition(":")
            req = Request(text=text, metadata=meta,
                          max_new_tokens=max_new_tokens)
            req.route, req.action = rname, action
            req.generation = gen.gen_id
            if kind == "model" and target in self.backends:
                req.backend = self._admit_target(req, target, gen)
            elif kind == "plugin":
                req.backend = "__plugin__:" + target
                req.done = True          # plugins are terminal here
            else:
                req.backend = "__reject__"
                req.done = True
            if not req.done:
                gen.inflight += 1
                self.batcher.submit(req)
            reqs.append(req)
        return reqs

    def _admit_target(self, req: Request, target: str,
                      gen: PolicyGeneration) -> str:
        """Admission-time degradation: an open breaker re-routes the
        request to the policy's fallback before it ever queues."""
        if self.faults.is_open(target):
            fb = self._fallback_for(target, gen)
            if fb is not None:
                req.fallback_used = True
                if self.audit:
                    self.audit.log("reroute", backend=fb,
                                   query_hash=qhash(req.text),
                                   generation=gen.gen_id,
                                   detail={"from": target,
                                           "at": "admission"})
                return fb
        return target

    def _decode_batch(self, backend: str, batch: List[Request],
                      _fallback_ok: bool = True) -> int:
        """Guarded prefill + greedy decode of one batch on ``backend``:
        breaker admission gate, per-request retry with jittered backoff,
        degradation to the policy's fallback backend, and terminal
        ``failed`` marking when every option is exhausted.  Always
        completes every request (and its coalesced followers) one way or
        another.  -> #completed."""
        fm = self.faults
        gate = fm.admission(backend)
        attempts = (0 if gate == "open"
                    else 1 if gate == "probe"
                    else fm.retry.max_retries + 1)
        err: Optional[BaseException] = None
        for attempt in range(attempts):
            if attempt:
                time.sleep(fm.backoff_s(attempt - 1))
            for r in batch:            # a retry re-decodes from scratch
                r.output_tokens = []
                r.truncated = False
            try:
                fm.pre_call(backend)
                n = self._decode_batch_attempt(backend, batch)
                fm.record(backend, True)
                return n
            except Exception as e:  # noqa: BLE001 — containment boundary
                err = e
                fm.record(backend, False)
                for r in batch:
                    r.retries += 1
                if self.audit:
                    self.audit.log(
                        "fault", backend=backend,
                        detail={"error": f"{type(e).__name__}: {e}",
                                "attempt": attempt,
                                "batch": len(batch)})
        # retries exhausted (or breaker open): degrade, then fail
        fb = self._fallback_for(backend) if _fallback_ok else None
        if fb is not None:
            for r in batch:
                r.backend = fb
                r.fallback_used = True
            if self.audit:
                self.audit.log("reroute", backend=fb,
                               detail={"from": backend,
                                       "batch": len(batch)})
            return self._decode_batch(fb, batch, _fallback_ok=False)
        msg = (f"circuit breaker open on backend {backend!r}"
               if attempts == 0
               else f"{type(err).__name__}: {err}")
        return self._fail_batch(batch, msg)

    def _fail_batch(self, batch: List[Request], msg: str) -> int:
        """Terminal failure for a contained batch: requests are marked
        ``failed`` with the error recorded and finish normally (audit +
        refcount via the done-hook) — the serve loop moves on."""
        now = self.cbatcher.clock()
        n = 0
        for r in batch:
            r.failed = True
            r.error = msg
            self.cbatcher.finish_inflight(r)
            n += finish_request(r, now=now, on_done=self._on_request_done)
        return n

    def _decode_batch_attempt(self, backend: str,
                              batch: List[Request]) -> int:
        """One unguarded prefill + greedy decode attempt (the pre-fault-
        tier ``_decode_batch`` body).

        Decode steps are clamped to the KV budget: step ``j`` writes
        cache position ``plen + j``, so a long prompt plus a large
        ``max_new_tokens`` must never advance past ``rt.max_seq`` (it
        would silently corrupt the prefill cache).  Clamped requests are
        flagged ``truncated``."""
        rt = self.backends[backend]
        cfg = rt.model.cfg
        # tokenize: byte-level prompt, pad to common length
        toks = [list(t.encode("utf-8"))[: rt.max_seq // 2] for t in
                (r.text for r in batch)]
        plen = max(max(len(t) for t in toks), 1)
        prompt = np.zeros((len(batch), plen), np.int32)
        for i, t in enumerate(toks):
            prompt[i, plen - len(t):] = [b % cfg.vocab_size for b in t]
        logits, cache = rt.prefill(rt.params, jnp.asarray(prompt))
        pos = plen
        tok = jnp.argmax(logits, axis=-1)[:, None].astype(jnp.int32)
        kv_room = max(0, rt.max_seq - plen)
        budgets = []
        for r in batch:
            budgets.append(min(r.max_new_tokens, kv_room))
            if budgets[-1] < r.max_new_tokens:
                r.truncated = True
        for _ in range(max(budgets)):
            for i, r in enumerate(batch):
                if len(r.output_tokens) < budgets[i]:
                    r.output_tokens.append(int(tok[i, 0]))
            logits, cache = rt.decode(rt.params, cache, tok, pos)
            tok = jnp.argmax(logits, axis=-1)[:, None].astype(jnp.int32)
            pos += 1
        now = self.cbatcher.clock()
        return sum(finish_request(r, now=now,
                                  on_done=self._on_request_done)
                   for r in batch)

    def step(self) -> int:
        """Serve one batch from the fullest backend queue.  -> #completed."""
        nb = self.batcher.next_batch()
        if nb is None:
            return 0
        return self._decode_batch(*nb)

    def drain(self) -> int:
        """Serve ``step`` until the one-shot queues empty.
        -> #completed."""
        n = 0
        while self.batcher.pending():
            n += self.step()
        return n

    # ---- continuous batching ----------------------------------------------
    def _effective_cap(self) -> Optional[int]:
        """The admission queue cap in effect (brownout L1+ shrinks it)."""
        if self.brownout is not None:
            return self.brownout.effective_cap(self.queue_cap)
        return self.queue_cap

    def _queue_depth(self, backend: str) -> int:
        """Requests waiting on ``backend``: admission queue + the slot
        scheduler's evicted-re-prefill queue."""
        depth = len(self.cbatcher.queues.get(backend, ()))
        if self.scheduler is not None:
            depth += len(self.scheduler.requeue.get(backend, ()))
        return depth

    def _shed(self, req: Request, reason: str, now: float) -> None:
        """Reject ``req`` at admission: terminal immediately, with an
        explicit reason, an audit ``shed`` record, and no generation
        refcount (it was never admitted)."""
        req.shed = True
        req.shed_reason = reason
        req.done = True
        req.finish_s = now
        self.overload["shed"] += 1
        if self.audit:
            self.audit.log("shed", generation=req.generation,
                           query_hash=qhash(req.text), route=req.route,
                           backend=req.backend,
                           detail={"reason": reason})

    def enqueue(self, texts: Sequence[str], metadata=None,
                max_new_tokens: int = 8,
                slo_ms: Optional[float] = None,
                timeout_s: Optional[float] = None,
                now: Optional[float] = None) -> List[Request]:
        """Admit a batch into the continuous-batching service loop.

        Routes the whole batch through the fused signal+policy program
        once (duplicate texts are free: the embedder LRU and the
        batcher's in-flight coalescing both key on the exact text),
        stamps each request's deadline from ``slo_ms``, its hard expiry
        from ``timeout_s`` (past which the sweep finishes it as
        ``timed_out``), and its policy generation (the hot-swap
        refcount), and admits model-bound requests into the per-backend
        admission queues — re-routed at admission when the target's
        breaker is open, and **shed** (terminal, ``shed_reason`` set)
        instead of queued when the backend's queue is at the effective
        cap (``queue_cap``, tightened under brownout).  A duplicate of
        an in-flight text always coalesces — riding a leader costs no
        slot, so it is never shed.  Plugin/reject actions complete
        immediately, exactly like ``submit``.  Call
        ``serve_step``/``serve_forever`` to decode.
        """
        metadata = metadata or [None] * len(texts)
        now = self.cbatcher.clock() if now is None else now
        gen = self._gen
        indices, _ = self._route_eval(texts, metadata, gen=gen)
        reqs = []
        for text, meta, i in zip(texts, metadata, indices):
            action = gen.tables.action_key(i)
            kind, _, target = action.partition(":")
            req = Request(text=text, metadata=meta,
                          max_new_tokens=max_new_tokens,
                          arrival_s=now,
                          deadline_s=(now + slo_ms / 1e3
                                      if slo_ms is not None else None),
                          expire_s=(now + timeout_s
                                    if timeout_s is not None else None))
            req.route = gen.tables.rule_name(i)
            req.action = action
            req.generation = gen.gen_id
            if kind == "model" and target in self.backends:
                req.backend = self._admit_target(req, target, gen)
                cap = self._effective_cap()
                key = (req.backend, req.text, req.max_new_tokens)
                if cap is not None \
                        and key not in self.cbatcher._inflight \
                        and self._queue_depth(req.backend) >= cap:
                    self._shed(req, f"queue_full:{req.backend}", now)
                else:
                    self.overload["accepted"] += 1
                    gen.inflight += 1
                    self.cbatcher.admit(req, now=now)
            elif kind == "plugin":
                req.backend = "__plugin__:" + target
                req.done = True          # plugins are terminal here
                self.overload["accepted"] += 1
            else:
                req.backend = "__reject__"
                req.done = True
                self.overload["accepted"] += 1
            reqs.append(req)
        return reqs

    def serve_step(self, now: Optional[float] = None,
                   force: bool = False) -> int:
        """One continuous-batching service step.

        Whole-batch mode (``slots=None``): release the most
        urgent/loaded ready batch (deadline- and wait-aware) and decode
        it to completion; ``force=True`` drains under-full queues
        immediately.

        Slot mode (``slots=N``): one preemptible scheduler step —
        admissions/preemptions between decode steps, ONE pooled decode
        step across the active slots, immediate retirement of finished
        requests (``force`` is moot: admission is per-slot, never held
        for a full batch).

        Both modes first observe brownout pressure (when the ladder is
        on) and sweep cancelled/expired requests out of the admission
        queues; slot mode additionally frees the decode slots and KV
        rows of cancelled/expired in-flight requests (whole-batch mode
        decodes each released batch to completion, so mid-decode
        cancellation only takes effect at batch boundaries there).
        -> #requests completed (coalesced followers included)."""
        now = self.cbatcher.clock() if now is None else now
        if self.brownout is not None:
            self.brownout.observe(now)
        if self.scheduler is not None:
            return self.scheduler.step(now=now)
        self.cbatcher.sweep_terminal(
            now, lambda r: self._finish_overload(r, now))
        nb = self.cbatcher.next_batch(now=now, force=force)
        if nb is None:
            return 0
        return self._decode_batch(*nb)

    def _finish_overload(self, req: Request, now: float) -> int:
        """Finalize a swept (cancelled or expired) request: terminal
        flags, audit record, follower fan-out, generation refcount via
        ``_on_request_done``.  -> #requests finished."""
        if req.cancelled:
            req.error = req.error or "cancelled by client"
        else:
            req.timed_out = True
            req.error = req.error or "request timeout"
        if self.audit:
            self.audit.log(
                "cancel" if req.cancelled else "timeout",
                generation=req.generation, query_hash=qhash(req.text),
                route=req.route, backend=req.backend,
                detail={"tokens": len(req.output_tokens),
                        "expire_s": req.expire_s})
        return finish_request(req, now=now,
                              on_done=self._on_request_done)

    def _has_pending_work(self) -> bool:
        if self.scheduler is not None:
            return self.scheduler.pending()
        return self.cbatcher.pending() > 0

    def telemetry(self) -> Dict[str, Any]:
        """One structured snapshot of the service's observable state.

        The contract the workloads ``DiagnosticsManager`` records each
        serve step (docs/workloads.md documents the JSONL schema built
        from it).

        Returns:
            Dict with ``queue_depth`` (waiting requests per backend),
            ``batcher`` (admission counters), and — when the matching
            subsystem is on — ``scheduler`` (slot-scheduler counters),
            ``requeue`` (evicted requests per backend), ``slots``
            (per-backend occupancy), ``breakers`` (circuit state per
            backend), ``generations`` (hot-swap refcounts), and
            ``audit`` (records logged per kind), plus ``ingress``
            (overload counters: accepted/shed/timed_out/cancelled and
            the current ``brownout_level``), ``route`` (cumulative:
            ``calls``, ``rows`` routed, ``bucket_rows`` after padding,
            and ``phases``, per ``router.*`` phase of ``_route_eval``
            its ``n``, ``seconds`` and ``over_100ms``), ``build``
            (seconds per ``router.build.*`` phase of the serving
            generation's build) and ``policy`` (the serving
            generation's compiled-policy shape,
            ``PolicyTables.stats``: rules, terms, literals, padding
            widths ``M``/``Kp``/``Kn`` and ``pad_share``), and, with
            an on-device embedder, ``encoder`` (its launches, real and
            launched tokens, ``pad_share``, launches per length bucket
            and the ``router.encode`` phase;
            ``EncoderEmbedder.telemetry``).
        """
        out: Dict[str, Any] = {
            "queue_depth": {b: len(q) for b, q in
                            self.cbatcher.queues.items()},
            "batcher": dict(self.cbatcher.stats),
            "generations": self.generations(),
            "ingress": {**self.overload,
                        "brownout_level": (self.brownout.level
                                           if self.brownout else 0)},
            "route": {"calls": self._route_phases.count("router.route"),
                      "rows": self._route_rows,
                      "bucket_rows": self._route_bucket_rows,
                      "phases": self._route_phases.snapshot()},
            "build": dict(self._gen.build_s),
            "policy": self._gen.tables.stats(),
        }
        encoder = getattr(self.embedder, "telemetry", None)
        if encoder is not None:
            out["encoder"] = encoder()
        if self.scheduler is not None:
            out["scheduler"] = dict(self.scheduler.stats)
            out["requeue"] = {b: len(q) for b, q in
                              self.scheduler.requeue.items() if q}
            out["slots"] = self.scheduler.slot_occupancy()
        if self.faults is not None and self.faults.breakers:
            out["breakers"] = self.faults.states()
        if self.audit is not None:
            out["audit"] = self.audit.counts()
        return out

    def serve_forever(self, *, max_steps: Optional[int] = None,
                      stop_when_idle: bool = True,
                      poll_s: float = 0.0005) -> int:
        """Drive ``serve_step`` until idle (or ``max_steps`` loop
        iterations — decoded batches and idle polls both count, so the
        bound caps runtime even when traffic stops).

        The benchmark/driver-facing loop: admission continues from other
        callers of ``enqueue`` between steps.  When a queue is neither
        full nor past its wait/deadline budget the loop sleeps
        ``poll_s`` and lets it age — wait-based urgency guarantees every
        queued request is eventually released, so no forced flush is
        needed.  -> total #completed.
        """
        import time as _time
        served = 0
        steps = 0
        while max_steps is None or steps < max_steps:
            steps += 1
            n = self.serve_step()
            if n:
                served += n
                continue
            if not self._has_pending_work() and stop_when_idle:
                break
            if self.scheduler is not None and self.scheduler.pending():
                continue              # slots mid-decode: step again now
            _time.sleep(poll_s)
        return served
