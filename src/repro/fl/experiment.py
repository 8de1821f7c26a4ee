"""``Experiment``: compile an ``ExperimentSpec`` into a runnable object.

    spec = ExperimentSpec(fl=FLConfig(scheme="normalized", case="II"),
                          data=DataSpec(dataset="ridge"))
    e = Experiment(spec)
    e.run(300)                      # setup() is implicit on first run
    e.history["gap"]                # accumulated across run() calls
    e.save("ckpt.msgpack")          # params + server-opt state + channel
    ...
    e2 = Experiment(spec); e2.load("ckpt.msgpack"); e2.run(300)  # resumes

One object drives both runtime drivers (``scan``/``python``) and all three
execution backends; with the default axes (``server_opt='sgd'``,
``local_steps=1``, ``participation=1.0``) the produced history is exactly
``repro.fed.runtime.run``'s (bitwise on CPU) — the facade adds declaration,
not new math.
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional

import jax
import numpy as np

from repro import obs
from repro.checkpoint import store
from repro.fed import runtime
from repro.fl.spec import ExperimentSpec
from repro.fl.tasks import Task, build_task

PyTree = Any


class Experiment:
    """A declarative OTA-FL experiment: spec -> setup() -> run(num_rounds).

    ``history`` accumulates every per-round diagnostic and eval metric across
    ``run()`` calls; ``save()``/``load()`` checkpoint the full resumable
    state (model params, server-optimizer state, channel/round) through
    ``repro.checkpoint.store``.

    Checkpoints are portable across PHYSICAL device counts: the spec's
    ``device_mesh`` defines the round's accumulation order (the math), not
    where it runs, and the checkpoint tree carries no placement — so a run
    saved on a 4-device host resumes bitwise-identically on 1 device (the
    sharded engine falls back to its emulated path; see
    ``FLConfig.device_mesh`` and tests/test_sharded_streaming.py).
    """

    def __init__(self, spec: ExperimentSpec, task: Optional[Task] = None,
                 recorder: Optional[obs.Recorder] = None):
        self.spec = spec
        self.cfg = spec.fl_config()
        # a caller that already built the task (the sweep engine's sequential
        # fallback, parity tests) may inject it; it must match the spec —
        # build_task's lru cache makes the default path equally shared
        self.task: Optional[Task] = task
        self.state: Optional[runtime.FLState] = None
        self.history: Dict[str, List] = {}
        # default flight-recorder sink for every run() (a per-call recorder
        # overrides it); telemetry is trajectory-invisible, see repro.obs
        self.recorder: Optional[obs.Recorder] = recorder

    # ------------------------------------------------------------------ setup

    def setup(self) -> "Experiment":
        """Build (or fetch the cached) task, draw the channel, and run the
        paper's parameter optimization (Problem 3 / Algorithm 1)."""
        if self.task is None:
            self.task = build_task(self.spec.data, self.spec.model,
                                   self.cfg.num_devices)
        self.state = runtime.setup(self.cfg, self.task.params0,
                                   self.task.model_dim)
        self.history = {}
        return self

    def reset(self) -> "Experiment":
        """Re-setup from round 0 (fresh params/optimizer/channel state); the
        cached task — and therefore the compiled executables keyed on its
        ``grad_fn`` — is reused."""
        return self.setup()

    def _ensure_setup(self):
        if self.state is None:
            self.setup()

    # -------------------------------------------------------------------- run

    def run(self, num_rounds: int, *, driver: Optional[str] = None,
            chunk_size: Optional[int] = None,
            eval_every: Optional[int] = None,
            evaluate: Optional[bool] = None,
            recorder: Optional[obs.Recorder] = None) -> Dict[str, List]:
        """Run ``num_rounds`` FL rounds and merge the produced history into
        ``self.history``.  Keyword overrides exist for benchmarking both
        drivers from one spec; experiments normally declare everything in
        the spec.  Returns this call's history (the increment, not the
        accumulated ``self.history``).

        ``recorder`` (or the constructor's default) streams the run live —
        one manifest event, then chunk/round/eval events from the engine;
        with ``REPRO_OBS_PROFILE`` set, the whole call is wrapped in a
        ``jax.profiler`` trace.  Under any active trace the call is the
        ``fl.run`` span.  All are trajectory-invisible."""
        handle = obs.profiling.start_profile()
        try:
            with obs.profiling.span("fl.run"):
                self._ensure_setup()
                ev = self.spec.eval
                enabled = ev.enabled if evaluate is None else evaluate
                rec = recorder if recorder is not None else self.recorder
                if rec is not None:
                    rec.on_manifest(self.manifest())
                self.state, hist = runtime.run(
                    self.cfg, self.state, self.task.grad_fn,
                    self.task.batch_provider, num_rounds,
                    eval_fn=self.task.eval_fn if enabled else None,
                    eval_every=(eval_every if eval_every is not None
                                else ev.every),
                    driver=driver or self.spec.driver,
                    chunk_size=chunk_size or self.spec.chunk_size,
                    chunk_batch_provider=self.task.chunk_batch_provider,
                    recorder=rec)
                for k, v in hist.items():
                    self.history.setdefault(k, []).extend(v)
        finally:
            obs.profiling.stop_profile(handle)
        return hist

    # ---------------------------------------------------------- observability

    def manifest(self) -> Dict[str, Any]:
        """This experiment's run manifest: spec JSON, config hash,
        structural signature, the current params digest, and the jax /
        platform identity block (see :mod:`repro.obs.manifest`)."""
        self._ensure_setup()
        return obs.run_manifest(spec=self.spec, cfg=self.cfg,
                                params=self.state.params,
                                extra={"round": int(self.state.round)})

    def dump_history(self, path: str) -> str:
        """Write the accumulated ``self.history`` to ``path`` as the same
        JSONL event stream a live :class:`repro.obs.JsonlRecorder` produces
        (manifest line, then one ``round`` line per round and one ``eval``
        line per eval boundary) — post-hoc telemetry for runs that did not
        record live."""
        self._ensure_setup()
        diag_keys = [k for k in runtime.DIAG_KEYS if k in self.history]
        eval_keys = [k for k in self.history
                     if k not in ("round", "eval_round")
                     and k not in runtime.DIAG_KEYS]
        with obs.JsonlRecorder(path) as rec:
            rec.on_manifest(self.manifest())
            for j, t in enumerate(self.history.get("round", [])):
                rec.on_round(int(t), {k: self.history[k][j]
                                      for k in diag_keys})
            for j, t in enumerate(self.history.get("eval_round", [])):
                rec.on_eval(int(t), {k: self.history[k][j]
                                     for k in eval_keys})
        return path

    # ------------------------------------------------------------- properties

    @property
    def params(self) -> PyTree:
        self._ensure_setup()
        return self.state.params

    @property
    def round(self) -> int:
        return 0 if self.state is None else self.state.round

    # ------------------------------------------------------------ checkpoints

    def _ckpt_tree(self) -> PyTree:
        st = self.state
        channel = {
            "h": np.asarray(st.h, np.float64),
            "b": np.asarray(st.b, np.float64),
            "a": np.asarray(st.a, np.float64),
            "eta0": np.asarray(st.eta0, np.float64),
            # the server's CSI estimate (== h under perfect CSI)
            "h_hat": np.asarray(st.h_hat if st.h_hat is not None else st.h,
                                np.float64),
        }
        # optional wireless-environment state: present iff the spec's
        # channel model/geometry produces it, so the tree structure is a
        # function of the spec alone (save/load on equal specs round-trips)
        if st.fad_state is not None:
            channel["fad_state"] = np.asarray(st.fad_state, np.float64)
        if st.scale is not None:
            channel["scale"] = np.asarray(st.scale, np.float64)
        out = {"params": st.params, "opt": st.opt_state, "channel": channel}
        # client-algorithm state (repro.fl.clients): present iff the spec's
        # algorithm is stateful — again a function of the spec alone
        if st.client_state is not None:
            out["client"] = jax.tree_util.tree_map(
                lambda l: np.asarray(l, np.float32), st.client_state)
        return out

    def save(self, path: str) -> str:
        """Checkpoint params + server-optimizer state + channel/round so a
        fresh ``Experiment`` on the same spec can ``load`` and resume the
        exact trajectory."""
        self._ensure_setup()
        if self.state.opt_state is None:
            # run() initializes lazily; a save before any run records step 0
            self.state.opt_state = runtime.server_optimizer(
                self.cfg).init(self.state.params)
        store.save(path, self._ckpt_tree(),
                   {"round": self.state.round,
                    "model_dim": self.state.model_dim,
                    "scheme": self.cfg.scheme,
                    "server_opt": self.cfg.server_opt})
        return path

    def load(self, path: str) -> "Experiment":
        """Restore a checkpoint written by ``save`` (shape/dtype checked
        against this spec's params and optimizer structure) and position the
        experiment at the checkpoint's round.  Non-strict on two scoped
        prefixes only: checkpoints from before the wireless-environment
        subsystem lack ``h_hat``/``fad_state``/``scale`` and keep the
        ``setup()`` values (exact for the default environment they were
        written under), and checkpoints from before the client-algorithm
        registry lack the ``['client']`` subtree and keep ``setup()``'s zero
        client state (exactly what those runs carried implicitly); a
        params/optimizer structure mismatch still fails loudly."""
        self._ensure_setup()
        if self.state.opt_state is None:
            self.state.opt_state = runtime.server_optimizer(
                self.cfg).init(self.state.params)
        restored, meta = store.restore(
            path, self._ckpt_tree(),
            # ONLY the post-subsystem leaves may be absent; a checkpoint
            # missing h/b/a/eta0 (or params/opt leaves) still fails loudly
            missing_ok=("['channel']['h_hat']", "['channel']['fad_state']",
                        "['channel']['scale']", "['client']"))
        st = self.state
        st.params = restored["params"]
        st.opt_state = restored["opt"]
        st.h = np.asarray(restored["channel"]["h"], np.float64)
        st.b = np.asarray(restored["channel"]["b"], np.float64)
        st.a = float(restored["channel"]["a"])
        st.eta0 = float(restored["channel"]["eta0"])
        st.h_hat = np.asarray(restored["channel"]["h_hat"], np.float64)
        if "fad_state" in restored["channel"]:
            st.fad_state = np.asarray(restored["channel"]["fad_state"],
                                      np.float64)
        if "scale" in restored["channel"]:
            st.scale = np.asarray(restored["channel"]["scale"], np.float64)
        if "client" in restored:
            st.client_state = restored["client"]
        st.round = int(meta["round"])
        return self
