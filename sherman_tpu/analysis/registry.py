"""The repo-specific knowledge shermanlint's rules consult.

Rules are generic mechanisms ("no host sync in a registered hot
function"); THIS module is where the repo names its hot functions,
pool mutators, append paths and obs increment paths.  Patterns are
``fnmatch`` globs over ``(repo-relative path, dotted qualname)`` —
see :func:`sherman_tpu.analysis.core.match_scope`.

Tests build their own :class:`Registry` pointing at fixture files, so
every rule is exercised without depending on the live tree's content.
"""

from __future__ import annotations

from dataclasses import dataclass, field


@dataclass
class Registry:
    # -- SL001: traced/per-step hot functions: no host syncs ------------------
    hot_functions: list[tuple[str, str]] = field(default_factory=list)
    #: attribute-chain roots whose reads are static Python config, not
    #: device arrays — ``int(cfg.machine_nr)`` is fine in a hot body
    static_roots: set[str] = field(default_factory=set)

    # -- SL002: dirty-threading contract --------------------------------------
    #: terminal callee/reference names that mutate the pool
    pool_mutators: set[str] = field(default_factory=set)
    #: compositions deliberately outside the durability contract
    dirty_allowlist: list[tuple[str, str]] = field(default_factory=list)

    # -- SL003: typed errors --------------------------------------------------
    #: path globs where bare stdlib raises are banned (library code)
    library_paths: list[str] = field(default_factory=list)
    banned_raises: set[str] = field(default_factory=lambda: {
        "ValueError", "RuntimeError", "AssertionError"})

    # -- SL004: retrace hazards at jit dispatch sites -------------------------
    #: callee-name globs whose RESULT is a compiled program
    jit_factory_patterns: list[str] = field(default_factory=list)

    # -- SL005: fsync-before-ack ----------------------------------------------
    append_paths: list[tuple[str, str]] = field(default_factory=list)
    fsync_names: set[str] = field(default_factory=lambda: {
        "fsync", "_fsync", "fdatasync", "_commit"})
    durable_write_names: set[str] = field(default_factory=lambda: {"write"})

    # -- SL006: no allocation in obs increment paths --------------------------
    obs_hot_functions: list[tuple[str, str]] = field(default_factory=list)

    # -- SL007: documented knobs ----------------------------------------------
    knob_prefix: str = "SHERMAN_"
    readme: str = "README.md"
    #: extra documentation files a knob may appear in instead
    knob_docs: list[str] = field(default_factory=list)
    #: when set, SL007 checks against this text instead of reading the
    #: doc files — the hook fixture tests use
    knob_doc_text: str | None = None


DEFAULT_REGISTRY = Registry(
    hot_functions=[
        # the device-step programs: traced under jit/shard_map — a host
        # sync here either breaks tracing or serializes every step
        ("sherman_tpu/models/batched.py", "descend_spmd"),
        ("sherman_tpu/models/batched.py", "search_routed_spmd"),
        ("sherman_tpu/models/batched.py", "search_spmd"),
        ("sherman_tpu/models/batched.py", "leaf_apply_spmd"),
        ("sherman_tpu/models/batched.py", "leaf_delete_apply_spmd"),
        ("sherman_tpu/models/batched.py", "_resolve_leaves"),
        ("sherman_tpu/models/batched.py", "_route_and_apply"),
        ("sherman_tpu/models/batched.py", "insert_step_spmd"),
        ("sherman_tpu/models/batched.py", "delete_step_spmd"),
        ("sherman_tpu/models/batched.py", "mixed_step_spmd"),
        ("sherman_tpu/parallel/dsm.py", "dsm_step_spmd"),
        ("sherman_tpu/parallel/dsm.py", "read_pages_spmd"),
        ("sherman_tpu/parallel/dsm.py", "_word_apply"),
        ("sherman_tpu/parallel/dsm.py", "_apply"),
        # the staged serving loops' per-step dispatch closures (PR 2/6):
        # one stray .item() here is a per-step device round-trip the
        # 33.8 M ops/s number does not survive
        ("sherman_tpu/workload/device_prep.py", "make_staged_step.step"),
        ("sherman_tpu/workload/device_prep.py", "make_staged_step.prep"),
        ("sherman_tpu/workload/device_prep.py", "make_staged_step.serve"),
        ("sherman_tpu/workload/device_prep.py", "make_staged_step.fused"),
        ("sherman_tpu/workload/device_prep.py", "make_staged_step.verify"),
        ("sherman_tpu/workload/device_prep.py",
         "make_staged_step.prep_core"),
        ("sherman_tpu/workload/device_prep.py",
         "make_staged_step.verify_core"),
        # mixed factory: per-step dispatch closures + traced cores only
        # (phase_profile / record_slo / new_carry are diagnostics that
        # legitimately touch host)
        ("sherman_tpu/workload/device_prep.py",
         "make_staged_mixed_step.step"),
        ("sherman_tpu/workload/device_prep.py",
         "make_staged_mixed_step.prep"),
        ("sherman_tpu/workload/device_prep.py",
         "make_staged_mixed_step.serve*"),
        ("sherman_tpu/workload/device_prep.py",
         "make_staged_mixed_step.verify*"),
        ("sherman_tpu/workload/device_prep.py", "_two_deep_slot.*"),
        # hot-key tier (PR 11): the probe/validate kernels are traced
        # (a host sync breaks tracing), and the staged cache_probe
        # closure rides the sealed per-step dispatch path
        ("sherman_tpu/models/leaf_cache.py", "probe_rows"),
        ("sherman_tpu/models/leaf_cache.py", "invalidation_mask"),
        ("sherman_tpu/models/leaf_cache.py", "slot_hash"),
        ("sherman_tpu/models/leaf_cache.py", "LeafCache._get_probe.kernel"),
        ("sherman_tpu/models/leaf_cache.py", "LeafCache._get_fill.kernel"),
        ("sherman_tpu/workload/device_prep.py",
         "make_staged_step.cache_probe"),
        # serving front door (PR 13): the per-step ingress dispatch
        # closures — the front door's continuous-batching loop runs one
        # of these per device step, so a stray host sync here serializes
        # every serving step on a device round trip (completion
        # belongs in the complete() half, which materializes by design)
        ("sherman_tpu/workload/device_prep.py",
         "make_ingress_step.dispatch"),
        # device-resident request plane (PR 17): the on-device prep
        # program family (combine/sort/route in one compiled ladder
        # rung) and the device-mode ingress dispatch closure — the
        # whole point of device prep is that nothing syncs before the
        # fused fan-out launches, so a stray host sync here re-creates
        # the host-prep serialization the knob exists to remove
        ("sherman_tpu/workload/device_prep.py",
         "make_device_prep.prep_core"),
        ("sherman_tpu/workload/device_prep.py", "make_device_prep.*"),
        ("sherman_tpu/workload/device_prep.py",
         "make_ingress_step.dispatch_device"),
        ("sherman_tpu/serve.py", "ShermanServer._take_reads"),
        ("sherman_tpu/serve.py", "ShermanServer._dispatch_reads"),
        # client-contract plane (PR 15): the dispatch-path queue pops
        # run per formed step under the admission lock — deadline
        # shedding and the fair-share take are plain pops/adds, and a
        # stray host sync here stalls every client behind the lock
        ("sherman_tpu/serve.py", "ShermanServer._take"),
        ("sherman_tpu/serve.py", "ShermanServer._shed_expired"),
        # value heap (PR 14): the handle-resolve kernels are traced
        # (the gather phase of the fused read fan-out), and the fused
        # program closure composes the descent + gather on device — a
        # host sync in either breaks tracing or serializes every
        # payload read
        ("sherman_tpu/models/value_heap.py", "resolve_rows"),
        ("sherman_tpu/models/value_heap.py",
         "ValueHeap._get_resolve.kernel"),
        ("sherman_tpu/models/value_heap.py",
         "ValueHeap._get_fused.kernel"),
        # replication plane (PR 16): the follower apply loop runs once
        # per poll for EVERY shipped record batch — a stray host sync
        # here turns replication lag into a per-record device
        # round-trip, and the lag gauge is a headline receipt number
        ("sherman_tpu/replica.py", "Follower.pump"),
        # partition plane (PR 18): the tailer poll loop runs per
        # shipping round per follower, now with the chaos-directive
        # and fence checks inline — a host sync here stalls every
        # follower's apply cadence and the quorum-ack wait that pumps
        # through it
        ("sherman_tpu/replica.py", "JournalTailer.poll"),
    ],
    static_roots={"cfg", "config", "self", "C", "D", "CFG", "bits",
                  "layout"},
    # the BOTTOM layer: these either scatter into the pool directly or
    # take dirty positionally as traced-kernel arguments.  Everything
    # composing them (insert/delete/mixed_step_spmd, engine closures)
    # is checked for the kw-only dirty= contract.
    pool_mutators={
        "_route_and_apply", "leaf_apply_spmd", "leaf_delete_apply_spmd",
        "writeback", "writeback_xla",
    },
    dirty_allowlist=[
        # PR 5 contract: device_prep/profiler compositions leave
        # dirty=None — bench-only, outside the durability contract
        ("sherman_tpu/workload/device_prep.py", "*"),
        ("sherman_tpu/chaos.py", "*"),
        ("tools/profile_insert.py", "*"),
        ("tools/profile_gather.py", "*"),
        ("tools/profile_staged2.py", "*"),
        ("tools/profile_prep.py", "*"),
    ],
    library_paths=["sherman_tpu/*"],
    jit_factory_patterns=["_get_*", "*_jit", "wrap_program"],
    append_paths=[
        ("sherman_tpu/utils/journal.py", "Journal.append"),
        # the client-contract ack records ride the same gate: an ack
        # cached in the dedup window must be durable before any future
        # resolves (PR 15)
        ("sherman_tpu/utils/journal.py", "Journal.append_acks"),
        # quorum acks (PR 18): the fence proxy is the SAME fsync
        # domain — it delegates every append to the wrapped segment
        # after the lease check, so a quorum ack released on its
        # return is released on durable bytes (SL005 sees the pure
        # delegation and the wrapped Journal.append's own fsync)
        ("sherman_tpu/replica.py", "_FencedJournal.append"),
        ("sherman_tpu/replica.py", "_FencedJournal.append_acks"),
    ],
    obs_hot_functions=[
        ("sherman_tpu/obs/registry.py", "Counter.inc"),
        ("sherman_tpu/obs/registry.py", "Gauge.set"),
        ("sherman_tpu/obs/registry.py", "Gauge.add"),
        ("sherman_tpu/obs/registry.py", "Histogram.record"),
        ("sherman_tpu/obs/slo.py", "LatencyTracker.record"),
        ("sherman_tpu/obs/slo.py", "WindowedRate.add"),
        ("sherman_tpu/obs/slo.py", "SloTracker.observe"),
        # hot-key tier: the per-probed-batch accounting path (plain
        # integer adds only — the cache.* collector allocates at PULL
        # time, which is off the hot path)
        ("sherman_tpu/models/leaf_cache.py", "LeafCache._note_probe"),
        # online migration (PR 12): the dirty-tracking hooks run inside
        # every checkpoint save (the sink) and every migration batch
        # (the poll) — plain set-folding loops; the migrate.* collector
        # allocates at PULL time like the cache's
        ("sherman_tpu/migrate.py", "Migrator._on_dirty_clear"),
        ("sherman_tpu/migrate.py", "Migrator._poll_dirt"),
        # serving front door (PR 13): the admission/serve accounting
        # runs on every submit and every completed batch inside the
        # open loop — plain integer adds only; the serve.* collector
        # allocates at PULL time like the cache's and migrate's
        ("sherman_tpu/serve.py", "ShermanServer._note_*"),
        # value heap (PR 14): per-batch put/get/free accounting —
        # plain integer adds; the heap.* collector allocates at PULL
        # time like every other collector
        ("sherman_tpu/models/value_heap.py", "ValueHeap._note_*"),
        # client-contract auditor (PR 15): the inline observe cost
        # accounting runs on every completed batch inside the serve
        # wall (the < 2% pin's own numerator must not allocate)
        ("sherman_tpu/audit.py", "Auditor._note_cost"),
        # write combining (PR 17): per-batch combined-kernel accounting
        # runs inside the insert/mixed write wall — plain integer adds;
        # the group/saved counts live in device counter slots and the
        # combine.* collector allocates at PULL time like every other
        ("sherman_tpu/models/batched.py",
         "BatchedEngine._note_combine_step"),
        # replication plane (PR 16): replica-read and fencing
        # accounting — _note_reads runs on every replica-tier read
        # batch and _note_fenced inside the durability gate's fence
        # check; plain integer adds, the repl.* collector allocates at
        # PULL time like every other collector
        ("sherman_tpu/replica.py", "ReplicaGroup._note_reads"),
        ("sherman_tpu/replica.py", "ReplicaGroup._note_fenced"),
        # quorum acks (PR 18): the wait accounting runs once per
        # quorum-gated ack inside the serve write wall (the latency-
        # delta receipt's own numerator) — plain adds only; the
        # server-side twin is covered by the ShermanServer._note_*
        # glob above
        ("sherman_tpu/replica.py", "ReplicaGroup._note_quorum"),
        # the one span API: the front door's dispatcher opens ~10 spans
        # a step (serve.take/prep/complete/idle and their children), so
        # opening, closing and recording a span build nothing beyond
        # the event tuple; the ring, the aggregates and the Chrome
        # export allocate at PULL time.  The per-request latency split
        # (ShermanServer._note_answer) rides the _note_* glob above.
        ("sherman_tpu/obs/spans.py", "_Span.__enter__"),
        ("sherman_tpu/obs/spans.py", "_Span.__exit__"),
        ("sherman_tpu/obs/spans.py", "SpanTracer._record"),
    ],
    knob_docs=["BENCHMARKS.md"],
)
