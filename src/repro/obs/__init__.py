"""Flight recorder: streaming telemetry, profiling hooks, and run manifests
for the compiled OTA-FL engine.

Three layers, all host-side:

* **Engine streaming** — ``repro.fed.runtime.run``/``run_batched`` accept a
  ``recorder`` and emit the per-chunk ``DIAG_KEYS`` series, eval metrics,
  per-chunk wall clock, dispatch counts, and re-trace attribution at chunk
  boundaries (after the on-device chunk returns — never inside the trace).
* **Profiling hooks** (:mod:`repro.obs.profiling`) — the engine's host
  spans (recorded under any active ``jax.profiler`` trace),
  ``REPRO_OBS_PROFILE`` env-gated traces around runs, plus the /proc RSS
  readers the K-scale benchmark pioneered.
* **Run manifests** (:mod:`repro.obs.manifest`) — spec JSON, structural
  signature, params sha-256, config hash, jax/platform versions: the
  identity block ``results/`` files and recorder streams carry.

The contract: telemetry is trajectory-invisible.  Recorder on vs off (any
sink) is bitwise-identical on params and history across both drivers, all
backends, ``k_block`` streaming, and ``device_mesh`` sharding — pinned by
``tests/test_obs.py`` and statically enforced by tracelint TL009.
"""
from .base import Recorder, get, make, names, register  # noqa: F401

# importing the sink module populates the registry (same idiom as
# repro.channels importing its model modules)
from .recorders import (CsvRecorder, JsonlRecorder,  # noqa: F401
                        MemoryRecorder, NullRecorder)

from . import manifest  # noqa: F401
from . import profiling  # noqa: F401
from .manifest import (config_sha256, params_sha256,  # noqa: F401
                       run_manifest, spec_json, structural_signature)
