"""One pane of glass: the process-wide telemetry bus (PR 9).

Three primitives, one correlated stream per run:

- **spans** — nested wall-clock phases (:func:`span`), async-dispatch
  aware (``block_on=`` a pytree, the ``utils/timers.py`` discipline)
  and mirrored into ``jax.named_scope`` so phase names land in on-chip
  profiler traces;
- **counters / gauges** — a labeled metric registry (:func:`counter`,
  :func:`gauge`) every subsystem publishes into: spectral-plan cache
  hits, engine fallbacks, checkpoint queue depth, supervisor retries,
  lane triage, replay verdicts, device-memory watermarks;
- **the run ledger** — a per-run append-only ``ledger.jsonl``
  (:class:`RunLedger`): spans close into it, counters snapshot into it
  at every chunk boundary, incidents and heartbeats cross-reference it
  by ``seq``, and every record carries the flight-recorder run
  fingerprint digest as ``run_id``.

The non-negotiable constraint: telemetry adds ZERO host transfers
inside the scanned chunk (pinned by the ``*_telemetry`` graph-contract
artifacts) and <2% warm-chunk wall overhead (self-accounted in
``RunLedger.overhead_s``, pinned like the flight recorder's). All
host-side work rides the existing one-transfer-per-chunk sync points.

PR 10 adds the read-back half: :mod:`ibamr_tpu.obs.deviceprof` parses
``jax.profiler`` captures and attributes device-lane op time back to
span paths (the ledger's ``device_time`` record / ``prof_summary.json``
artifact). It is offline and imported lazily here — attaching a
ledger to a run never pays for the trace parser.

PR 25: every closed span also lands in a bounded in-memory ring
(:func:`spans`) and on the profiler's timeline
(``jax.profiler.TraceAnnotation``); one ``jax.monitoring`` listener
records compiles and cache reads as spans; the run loop registers the
chunk programs it calls (:func:`programs`) so that ``deviceprof`` can
read their compiled text afterwards and join the chip's ``%fusion.N``
events to the phase names inside the step (``ib/prep`` ... ``fluid``).

PR 15 adds pod scope: ``RunLedger(..., proc=...)`` routes each process
of a multi-host run to its own ``ledger-<proc>.jsonl`` shard (same
``run_id`` everywhere), :mod:`ibamr_tpu.obs.merge` interleaves the
shards deterministically (``(seq, proc)`` order, torn-tail tolerant,
per-proc counter namespacing), and the device-time attribution grows a
``comm_s`` op-class so collective time is a first-class rollup.

See docs/OBSERVABILITY.md for the ledger schema and the CLI cookbook
(``tools/obs.py summary | tail | compare``,
``tools/prof.py attribute | diff | archive``).
"""

from ibamr_tpu.obs.bus import (  # noqa: F401
    HISTOGRAM_BOUNDS,
    Histogram,
    LEDGER_SCHEMA,
    RunLedger,
    annotate,
    attach,
    chunk_boundary,
    clear_spans,
    counter,
    current,
    current_trace,
    describe,
    detach,
    detached,
    emit,
    gauge,
    help_for,
    histogram,
    last_seq,
    ledger,
    metrics_snapshot,
    new_trace_id,
    peek_gauge,
    programs,
    quantiles_from_counts,
    read_ledger,
    record_trace_ids,
    register_program,
    reset_metrics,
    run_id_from_fingerprint,
    sample_memory_watermarks,
    shard_path,
    span,
    spans,
    trace_scope,
)
from ibamr_tpu.obs.export import (  # noqa: F401
    prometheus_text,
    write_prometheus,
)
from ibamr_tpu.obs.merge import (  # noqa: F401
    find_shards,
    fleet_counters,
    fleet_prometheus_text,
    merge_ledgers,
)
