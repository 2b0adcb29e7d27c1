"""The fleet flight recorder (ISSUE 20): always-on telemetry history,
crash forensics, and SLO burn-rate rollup.

This package is the ONLY telemetry-persistence site in kubetorch_tpu —
pinned by a ``check_resilience.py`` lint: ``REGISTRY.snapshot()`` and
``active_spans()`` (the persistence-feeding telemetry APIs) may be called
nowhere else. Everything that writes telemetry state to disk rides one of
these seams:

- :mod:`recorder` — the per-process background flight recorder: delta-
  encoded snapshots of the metrics registry + recently-completed spans,
  appended to a bounded hash-chained JSONL spool, with atexit/signal/
  watchdog hooks that flush a final record so even a crashed process
  leaves a readable black box.
- :mod:`blackbox` — the read side: verify a spool's hash chains and seq
  continuity, reconstruct the dead process's final metric snapshot and
  in-flight spans, render the ``kt blackbox`` report.
- :mod:`fleet` — the controller-side aggregator: merges per-pod
  ``kt_stage_seconds`` histograms across replicas (counter-reset aware),
  computes multi-window SLO burn rates, and emits typed
  :class:`~kubetorch_tpu.exceptions.SloBurnAlert` records.
"""

from .blackbox import (format_blackbox, metric_diff, read_spool,  # noqa: F401
                       reconstruct, spool_dirs, spool_identity,
                       verify_spool)
from .fleet import (CounterEpochs, FleetAggregator,  # noqa: F401
                    merge_histograms)
from .recorder import (FlightRecorder, apply_delta, chain_hash,  # noqa: F401
                       maybe_start_recorder, note_death, recorder,
                       snapshot_delta)
