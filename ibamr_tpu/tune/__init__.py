"""Measured-search autotuner (docs/TUNING.md).

No static engine choice is right across the size range — the r5
on-chip shootout ranking *inverts* between 128^3 and 256^3 (PERF.md).
This package replaces hand-picked promotions with measurement in the
FFTW/ATLAS tradition:

- :mod:`ibamr_tpu.tune.space` — candidate enumeration with static
  pruning (tile divisibility, minimum extents, wall-BC bf16 refusal,
  Pallas compile-probe gating), so the search never times a candidate
  that can't ship;
- :mod:`ibamr_tpu.tune.runner` — measured trials compiled through the
  AOT executable cache (compile paid once per candidate family), warm
  steps timed under ``obs.span`` with the async-dispatch block-on
  discipline, per-trial ``tune_trial`` ledger records;
- :mod:`ibamr_tpu.tune.db` — the versioned, provenance-stamped
  ``TUNING_DB.json`` the resolver
  (:mod:`ibamr_tpu.models.engine_resolver`) consults: schema v1
  validation, shadowed-entry lint, atomic publication.

``tools/tune.py`` is the CLI (search/show/publish/check); run
``search --publish`` on the chip so the committed defaults stay
device-measured.
"""

from ibamr_tpu.tune.db import (load_db, make_entry, make_provenance,
                               merge_entry, save_db, shadowed_entries,
                               validate_db)
from ibamr_tpu.tune.space import Candidate, enumerate_space
from ibamr_tpu.tune.runner import TrialResult, run_trial, search

__all__ = [
    "Candidate", "TrialResult", "enumerate_space", "load_db",
    "make_entry", "make_provenance", "merge_entry", "run_trial",
    "save_db", "search", "shadowed_entries", "validate_db",
]
