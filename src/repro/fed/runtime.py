"""Federated-learning runtime (paper Sec. II, Steps 1-3, iterated).

The K devices are a ``jax.vmap`` axis; one round (local computation -> OTA
superposition -> server update -> broadcast) is a single jittable program.
``FLConfig.backend`` selects which execution backend the aggregation routes
through — ``vmap`` (pure XLA), ``kernels`` (fused Pallas path; the default
for benchmarks), or ``mesh`` (shard_map/psum over local devices; needs >= K
of them).  The production mesh train-step builder (devices = data shards of
a TPU mesh) lives in ``repro.launch.train``.

Beyond the paper's eq. 10-11 round, the round math carries three scenario
axes, all spec fields (no new positional arguments — the declarative
``repro.fl.ExperimentSpec`` facade is the intended front door):

``server_opt``      the server applies a pluggable ``optim.Optimizer`` to
                    the OTA-aggregated direction, its state threaded through
                    the scan carry (donated buffers).  ``'sgd'`` (default,
                    momentum 0) IS eq. 11, ``w <- w - eta_t y``, exactly.
``local_steps``     H > 1: each client takes H local SGD steps (FedAvg-style,
                    arXiv:2310.10089) and transmits the accumulated model
                    delta ``(w - w_k^H) / (H * local_lr)`` — an average local
                    gradient — through the unchanged scheme registry (the
                    ``normalized`` scheme then aggregates the *normalized*
                    accumulated delta).
``participation``   per-round Bernoulli or fixed-fraction device masks
                    (arXiv:2409.07822-style partial client participation),
                    folded into the superposition weights AND the eq.-8
                    energy accounting via ``ota.participation_fold`` — a
                    masked device transmits nothing and spends nothing.

The radio environment itself is an axis too (``repro.channels``, all fields
on ``FLConfig.channel``): the fading process comes from the channel-model
registry (``channel.model`` — i.i.d. Rayleigh, Rician, or time-correlated
AR(1) whose Gauss-Markov state threads the scan carry and ``FLState``),
per-device means from drawn cell geometry (``channel.geometry``), and
imperfect CSI (``channel.csi_error``) splits the TRUE ``h_t`` the air
superposes with from the server ESTIMATE ``h_hat_t`` on which Algorithm 1,
the receiver gain, the participation rescale, and the side-info folding
run; the effective-gain misalignment this induces is the per-round
``csi_gain_err`` diagnostic.  ``rho`` and ``csi_error`` are batchable sweep
lanes (``BATCHED_CHANNEL_FIELDS``); model/geometry/K-factor are structural.

Two round-loop drivers (``run(..., driver=...)``):

``scan``   (default) the compiled multi-round engine: ``jax.lax.scan`` over
           rounds, dispatched in chunks whose param buffers are donated and
           whose per-round history lands in on-device arrays transferred
           once per chunk.  Under block fading the channel redraw
           (``core.channel.channel_for_round``) AND the Problem-3
           re-optimization (``core.amplification.solve_problem3_jax``, a
           ``lax.while_loop`` bisection) run *inside* the scan — the whole
           trajectory is one XLA program per chunk, no host callbacks.
``python`` the host-loop fallback: one jitted round per dispatch, history
           appended eagerly.  Use it when an ``eval_fn`` must observe every
           round or for step-debugging; it computes the identical numbers
           (tests/test_engine.py holds the two drivers to fp32 parity on
           every backend, fixed and block-fading).

Beyond single experiments, ``run_batched`` vectorizes the scan engine over a
leading *experiment* axis: E structurally-identical configs (same scheme /
case / backend / scenario axes — see ``structural_config``) that differ only
in *batchable numerics* (seed, eta, s_target, grad_bound, noise_var,
channel_mean, b_max, ...) compile into ONE program via ``jax.vmap`` through
``_round_math`` — including channel redraws and the Problem-3 bisection
under block fading — and the experiment axis is sharded across local
devices (``distribution.sharding.experiment_mesh``) when a mesh is
available.  ``repro.fl.sweep`` is the declarative front door that expands a
grid, groups points by structural signature, and dispatches here.
"""
from __future__ import annotations

import collections
import dataclasses
import functools
import os
import time
from functools import partial
from typing import Any, Callable, Dict, List, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import channels as chl
from repro.core import amplification as amp
from repro.core import channel as chan
from repro.core import ota
from repro.core import schemes
from repro.fl import clients as clientlib
from repro.obs import profiling as obsprof
from repro.optim import optimizers as optim

PyTree = Any
GradFn = Callable[[PyTree, Any], PyTree]   # (params, device_batch) -> grads

DRIVERS = ("scan", "python")
SERVER_OPTS = ("sgd", "adamw")
PARTICIPATION_MODES = ("bernoulli", "fixed")
# per-round scalar diagnostics recorded by BOTH drivers (same device-side
# math, so the drivers' histories agree exactly).  ``csi_gain_err`` is the
# relative misalignment of the realized effective gain a sum h_k b_k vs the
# one the server DESIGNED on its estimate, a sum h_hat_k b_k — exactly 0
# under perfect CSI, and the measurable cost of noisy/stale estimates.
DIAG_KEYS = ("grad_norm_mean", "grad_norm_min", "grad_norm_max", "eta",
             "update_norm", "tx_energy", "num_participants", "csi_gain_err")
# key-derivation salt separating the participation draw from the channel
# noise (both are folded from the same per-run key at the same round t)
_MASK_SALT = 0x5EED
# salt separating the CSI-estimation noise stream from the channel redraw
# (both fold from chan_key), and the geometry draw from the setup channel key
_CSI_SALT = 0xC51
_GEOM_SALT = 0x6E0
# salt separating the SECOND OTA transmission slot's channel-noise draw from
# the first's (multi-slot client algorithms, e.g. scaffold): slot 0 keeps
# the historical fold_in(key, t) BITWISE, slot 1 folds this salt on top —
# independent noise per slot, shared exactly by every backend and both the
# dense and streaming rounds
_SLOT_SALT = 0x510

# Compiled-executable cache size for the round/chunk builders below.  Large
# sweeps walk many (config, grad_fn) pairs; a too-small LRU silently evicts
# and re-traces mid-sweep, so the size is configurable without a code change
# (REPRO_ENGINE_CACHE_SIZE) and ``cache_info()`` exposes hit/miss/trace
# counters so benchmarks can assert zero re-traces.
ENGINE_CACHE_SIZE = int(os.environ.get("REPRO_ENGINE_CACHE_SIZE", "64"))

# incremented inside the traced bodies (tracing executes them; cached
# executions do not), so re-traces are observable even when they happen
# inside jax's own jit cache rather than the lru builders
TRACE_COUNTS: collections.Counter = collections.Counter()

# The documented, closed key set of ``TRACE_COUNTS`` — one key per cached
# builder in ``_CACHED_BUILDERS``.  Historically the chunk-scan counter key
# was a free-form string threaded through ``_make_chunk_scan``; normalizing
# to this enum-like set keeps the recorder's per-chunk re-trace attribution
# (and ``cache_info()['traces_delta']``) stable across refactors.
TRACE_KINDS = ("round_step", "run_chunk", "run_chunk_batched",
               "fading_refresh")

# per-kind counts at the last cache_info() call, for the delta report
_TRACE_SNAPSHOT: Dict[str, int] = {}


def _count_trace(kind: str) -> None:
    """Record one trace of a compiled builder body.  Runs at trace time
    (host-side, inside the traced function's Python execution); a key
    outside ``TRACE_KINDS`` is a programming error, not a new counter."""
    if kind not in TRACE_KINDS:
        raise ValueError(f"unknown trace kind {kind!r}; one of {TRACE_KINDS}")
    TRACE_COUNTS[kind] += 1


def trace_deltas(since: Dict[str, int]) -> Dict[str, int]:
    """Per-builder re-trace deltas vs a ``dict(TRACE_COUNTS)`` snapshot —
    the recorder's per-chunk retrace attribution."""
    return {k: int(TRACE_COUNTS[k]) - int(since.get(k, 0))
            for k in TRACE_KINDS}


def _engine_cache(fn):
    return functools.lru_cache(maxsize=ENGINE_CACHE_SIZE)(fn)


def cache_info() -> Dict[str, Any]:
    """Introspection for the compiled-executable caches: per-builder
    ``lru_cache`` statistics, cumulative trace counts (``TRACE_COUNTS``,
    keyed by ``TRACE_KINDS``), and ``traces_delta`` — the per-builder
    re-trace deltas since the previous ``cache_info()`` call (reset by
    ``clear_compile_caches``).  The sweep benchmark asserts the trace
    counters stay flat across repeated grid runs — i.e. zero re-traces once
    warm."""
    delta = trace_deltas(_TRACE_SNAPSHOT)
    _TRACE_SNAPSHOT.update({k: int(TRACE_COUNTS[k]) for k in TRACE_KINDS})
    return {
        "cache_size": ENGINE_CACHE_SIZE,
        "builders": {name: fn.cache_info()._asdict()
                     for name, fn in _CACHED_BUILDERS.items()},
        "traces": dict(TRACE_COUNTS),
        "traces_delta": delta,
    }


def clear_compile_caches() -> None:
    """Drop every cached builder (and its jitted executables) and reset the
    trace counters — test isolation / memory-pressure escape hatch."""
    for fn in _CACHED_BUILDERS.values():
        fn.cache_clear()
    TRACE_COUNTS.clear()
    _TRACE_SNAPSHOT.clear()


# FLConfig fields a batched (vmapped) run can vary per experiment: they are
# either consumed only by host-side ``setup`` (folded into the stacked
# h/b/a/eta0 inputs) or threaded through the compiled program as traced
# per-experiment scalars (``BatchAxes``).  Everything else — scheme, case,
# backend, schedule exponent, scenario axes — changes the traced program
# and is therefore *structural*: vary it across compiles, not lanes.
BATCHED_FL_FIELDS = ("seed", "eta", "s_target", "epsilon_target",
                     "grad_bound", "smoothness_L", "strong_convexity_M",
                     "expected_loss_drop", "theta_th")
BATCHED_CHANNEL_FIELDS = ("noise_var", "channel_mean", "b_max", "rho",
                          "csi_error")

# The structural complement: every FLConfig / ChannelConfig field must be
# claimed by exactly one of the BATCHED_* tables above or these tables
# (tracelint TL005 enforces the partition and that structural_config
# collapses precisely the batched lanes).  A new field that lands in neither
# is the "silently unbatched" bug: run_batched would accept configs that
# differ in it and fold them into one compiled program.
STRUCTURAL_FL_FIELDS = (
    "num_devices", "scheme", "backend", "case", "p", "channel",
    "amplification", "server_opt", "server_momentum", "server_b1",
    "server_b2", "server_eps", "server_weight_decay", "local_steps",
    "local_lr", "participation", "participation_mode", "k_block",
    "active_gather", "device_mesh", "client")
STRUCTURAL_CHANNEL_FIELDS = ("num_devices", "block_fading", "model",
                             "rician_k", "csi_error_model", "geometry")


class BatchAxes(NamedTuple):
    """Per-experiment traced scalars of a batched run (each field is [E] at
    the ``run_batched`` boundary and a scalar inside the vmapped body).
    ``None`` fields fall back to the baked ``FLConfig`` value — the
    single-experiment drivers pass ``over=None`` everywhere (geometry runs
    excepted: they thread their per-device [K] ``rayleigh_scale`` here), so
    default traces (and compiled executables) are untouched by the batching
    refactor."""

    noise_var: Optional[jax.Array] = None       # sigma^2 at the ES
    grad_bound: Optional[jax.Array] = None      # G (schemes that need it)
    b_max: Optional[jax.Array] = None           # per-device cap, time-varying
    rayleigh_scale: Optional[jax.Array] = None  # redraw scale: scalar or [K]
    rho: Optional[jax.Array] = None             # AR(1) per-round correlation
    csi_error: Optional[jax.Array] = None       # estimation-error magnitude
    client_mu: Optional[jax.Array] = None       # fedprox proximal strength
    client_alpha: Optional[jax.Array] = None    # feddyn regularization


@dataclasses.dataclass(frozen=True)
class FLConfig:
    num_devices: int = 20
    scheme: str = "normalized"
    backend: str = "vmap"             # 'vmap' | 'kernels' | 'mesh' (see core.ota)
    case: str = "I"                   # 'I' (eta_t = 1/t^p) or 'II' (constant eta)
    p: float = 0.75                   # Case-I schedule exponent (paper: 0.75)
    eta: float = 0.01                 # Case-II constant learning rate (paper: 0.01)
    theta_th: float = chan.DEFAULT_THETA_TH
    channel: chan.ChannelConfig = None
    seed: int = 0
    # amplification policy: 'optimal' (Algorithm 1 / Problem 3) or 'bmax'
    # (the no-optimization comparison of Fig. 1(a)/2(a): every b_k = b_k^max)
    amplification: str = "optimal"
    grad_bound: Optional[float] = None   # G, needed by benchmark1 + Case II
    # Case-II target: pick exactly one (s wins if both set)
    s_target: Optional[float] = None
    epsilon_target: Optional[float] = None
    # Case-I optimal-S inputs
    smoothness_L: float = 1.0
    strong_convexity_M: float = 1.0
    expected_loss_drop: float = 1.0
    # --- scenario axes (defaults reproduce the paper's round exactly) ------
    # server-side optimizer applied to the OTA-aggregated direction:
    # 'sgd' (momentum 0 == eq. 11) or 'adamw'
    server_opt: str = "sgd"
    server_momentum: float = 0.0
    server_b1: float = 0.9
    server_b2: float = 0.95
    server_eps: float = 1e-8
    server_weight_decay: float = 0.0
    # H local SGD steps per client per round; the transmitted quantity for
    # H > 1 is the accumulated model delta (w - w_k^H) / (H * local_lr)
    local_steps: int = 1
    local_lr: float = 0.01
    # expected participating fraction per round; 'bernoulli' masks each
    # device independently, 'fixed' schedules exactly round(p*K) devices
    participation: float = 1.0
    participation_mode: str = "bernoulli"
    # --- K-scale axes -------------------------------------------------------
    # Streaming round: compute gradients and fold them into the OTA
    # accumulator k_block devices at a time (lax.scan), so the round's
    # working set is O(k_block * N) instead of O(K * N).  None (default)
    # keeps the dense round bitwise-pinned.  Streaming == dense up to float
    # associativity of the blocked sums (tests/test_streaming.py).
    k_block: Optional[int] = None
    # Under fixed-mode partial participation, gather the scheduled
    # participants' batches BEFORE the local gradient computation, so
    # per-round compute scales with the active set m = round(p K), not K.
    # Bitwise-identical to the dense masked round (params, tx_energy,
    # num_participants); the grad-norm diagnostics then cover the
    # participants only (non-participants never compute a gradient).
    active_gather: bool = False
    # Sharded streaming (requires k_block): partition the round's K-blocks
    # over this many mesh shards.  The value DEFINES the hierarchical
    # accumulation order — each shard left-folds a contiguous run of
    # stream_length/k_block/device_mesh blocks, then ONE deterministic
    # cross-shard fold (``distribution.ota_collectives.fold_shards``) closes
    # eq. (10) — so the trajectory is a function of the config alone:
    # running on a physical mesh (``distribution.sharding.device_mesh``
    # finds the devices; shard_map) and the emulated single-device fallback
    # (outer lax.scan over shards) are BITWISE-identical, which is what lets
    # a checkpoint move between hosts with different device counts
    # (tests/test_sharded_streaming.py).  None (default) keeps the PR-6
    # flat left fold bitwise-pinned; device_mesh=D differs from it only by
    # the re-association of the blocked sums (documented-ulp, like
    # k_block itself vs dense).
    device_mesh: Optional[int] = None
    # --- client-algorithm axis (repro.fl.clients) --------------------------
    # what each device optimizes locally and transmits: 'sgd' (the paper's
    # round, bitwise-pinned default), 'fedprox', and the two-slot correctors
    # 'feddyn' / 'scaffold' (whose refreshed correction states ride a second
    # OTA slot to teach the server its state)
    client: clientlib.ClientConfig = None

    def __post_init__(self):
        if self.channel is None:
            object.__setattr__(self, "channel",
                               chan.ChannelConfig(num_devices=self.num_devices))
        if self.client is None:
            object.__setattr__(self, "client", clientlib.ClientConfig())
        alg = clientlib.get(self.client.algo)
        if alg.num_slots > 1:
            # the slot-2 scheme must exist AND be channel-borne: a baseline
            # (channel-bypassing) scheme has no superposition to de-gain
            if schemes.get(self.client.variate_scheme).baseline:
                raise ValueError(
                    f"variate_scheme {self.client.variate_scheme!r} is a "
                    "baseline (channel-bypassing) scheme; the second OTA "
                    "slot is a genuine transmission")
        if self.backend not in ota.BACKENDS:
            raise ValueError(f"unknown backend {self.backend!r}; "
                             f"one of {ota.BACKENDS}")
        schemes.get(self.scheme)   # raises ValueError naming the registry
        if self.case not in ("I", "II"):
            raise ValueError(f"unknown case {self.case!r}; one of ('I', 'II')")
        if self.amplification not in ("optimal", "bmax"):
            raise ValueError(f"unknown amplification {self.amplification!r}; "
                             "one of ('optimal', 'bmax')")
        if self.server_opt not in SERVER_OPTS:
            raise ValueError(f"unknown server_opt {self.server_opt!r}; "
                             f"one of {SERVER_OPTS}")
        if self.local_steps < 1:
            raise ValueError(f"local_steps must be >= 1, got {self.local_steps}")
        if not 0.0 < self.participation <= 1.0:
            raise ValueError("participation must lie in (0, 1], got "
                             f"{self.participation}")
        if self.participation_mode not in PARTICIPATION_MODES:
            raise ValueError(
                f"unknown participation_mode {self.participation_mode!r}; "
                f"one of {PARTICIPATION_MODES}")
        if self.active_gather:
            if self.participation_mode != "fixed":
                raise ValueError(
                    "active_gather needs a static active-set size: use "
                    "participation_mode='fixed' (bernoulli draws a random "
                    "count per round)")
            if self.participation >= 1.0:
                raise ValueError(
                    "active_gather requires participation < 1 (at p = 1 the "
                    "gather is a random permutation that reorders the K-way "
                    "sum; the dense path is the right tool)")
        if self.k_block is not None:
            if self.k_block < 1:
                raise ValueError(f"k_block must be >= 1, got {self.k_block}")
            if self.backend == "mesh":
                raise ValueError("the mesh backend's device axis IS the mesh "
                                 "— k_block streaming applies to the stacked "
                                 "(vmap/kernels) backends; to parallelize a "
                                 "streamed round over local devices use "
                                 "device_mesh (the sharded streaming engine)")
            s = self.stream_length()
            if s % min(self.k_block, s) != 0:
                raise ValueError(
                    f"k_block {self.k_block} must divide the streamed device "
                    f"axis ({s} = {'the active set' if self.active_gather else 'num_devices'})")
        if self.device_mesh is not None:
            if self.device_mesh < 1:
                raise ValueError(
                    f"device_mesh must be >= 1, got {self.device_mesh}")
            if self.k_block is None:
                raise ValueError(
                    "device_mesh shards the K-block stream — set k_block "
                    "(the dense round has no block axis to partition)")
            s = self.stream_length()
            nb = s // min(self.k_block, s)
            if nb % self.device_mesh != 0:
                raise ValueError(
                    f"device_mesh {self.device_mesh} must divide the "
                    f"stream's block count {nb} (= streamed axis {s} / "
                    f"k_block {min(self.k_block, s)}) — pick a k_block so "
                    "the block count is a multiple of the mesh size")

    def stream_length(self) -> int:
        """Length of the streamed device axis: the fixed active-set size
        ``round(p K)`` under ``active_gather``, else the full cohort K."""
        if self.active_gather:
            return max(1, int(round(self.participation * self.num_devices)))
        return self.num_devices


def structural_config(cfg: FLConfig) -> FLConfig:
    """The *structural signature* of a config: every batchable numeric field
    (``BATCHED_FL_FIELDS`` / ``BATCHED_CHANNEL_FIELDS``) collapsed to a fixed
    sentinel.  Two configs are batchable into one compiled program iff their
    structural signatures are equal; the batched chunk builder is cached on
    this signature, so every sub-batch of a sweep that shares a structure
    shares one executable.  ``grad_bound`` keeps its None-ness (present vs
    absent changes the traced program), not its value."""
    channel = dataclasses.replace(cfg.channel, noise_var=0.0,
                                  channel_mean=1.0, b_max=1.0, rho=0.0,
                                  csi_error=0.0)
    client = dataclasses.replace(cfg.client, mu=0.0, alpha=0.01)
    return dataclasses.replace(
        cfg, seed=0, eta=0.01, s_target=None, epsilon_target=None,
        grad_bound=None if cfg.grad_bound is None else 1.0,
        smoothness_L=1.0, strong_convexity_M=1.0, expected_loss_drop=1.0,
        theta_th=chan.DEFAULT_THETA_TH, channel=channel, client=client)


@dataclasses.dataclass
class FLState:
    params: PyTree
    h: np.ndarray
    b: np.ndarray
    a: float
    eta0: float                       # eta for case II; eta_t = eta0/t^p for case I
    round: int = 0
    # the real model dimension N, recorded at setup() time so block-fading
    # re-optimization solves Problem 3 with the true n (not a placeholder)
    model_dim: int = 0
    # server-side optimizer state (initialized lazily by run() for states
    # built before the server_opt axis existed); step counts rounds, so
    # Adam bias correction stays consistent across resumed runs
    opt_state: Optional[optim.OptState] = None
    # the server's channel ESTIMATE h_hat (imperfect CSI; None — states from
    # before the wireless-environment subsystem — means perfect CSI: h)
    h_hat: Optional[np.ndarray] = None
    # persistent fading-process state ([K, 2] Gauss-Markov I/Q pair for the
    # 'ar1' model; None for stateless models) — threads the scan carry and
    # checkpoints so run(5); run(5) continues run(10)'s correlated channel
    fad_state: Optional[np.ndarray] = None
    # per-device amplitude scales from the geometry subsystem ([K]; None
    # keeps the homogeneous scalar ChannelConfig.amplitude_scale())
    scale: Optional[np.ndarray] = None
    # client-algorithm state (repro.fl.clients): {"dev": [K, ...] stacked
    # per-client pytree or None, "srv": param-shaped server pytree or None};
    # None for stateless algorithms (sgd/fedprox) — the pre-registry carry
    # and checkpoint layout, bitwise.  Initialized lazily by run() for
    # states built before the client-algorithm axis existed.
    client_state: Optional[Dict[str, Any]] = None


def server_optimizer(cfg: FLConfig) -> optim.Optimizer:
    """The pluggable server-side optimizer of ``cfg.server_opt``.  The
    learning rate is always passed per-call (the paper's eta_t schedules live
    in ``_eta_t``), so the constructor lr is a placeholder."""
    if cfg.server_opt == "adamw":
        return optim.adamw(0.0, b1=cfg.server_b1, b2=cfg.server_b2,
                           eps=cfg.server_eps,
                           weight_decay=cfg.server_weight_decay)
    return optim.sgd(0.0, momentum=cfg.server_momentum)


def _setup_channel(cfg: FLConfig):
    """Host-side round-0 radio environment: per-device amplitude scales
    (geometry), the model's initial draw (+ fading state), and the server's
    CSI estimate ``h_hat``.  Returns ``(h, h_hat, fad_state, scale_vec)``
    with ``h``/``h_hat`` float64 [K]; ``h_hat`` IS ``h`` (same array) under
    perfect CSI, so the default path is bitwise-unchanged."""
    key = jax.random.PRNGKey(cfg.seed)
    ccfg = cfg.channel
    model = chl.get(ccfg.model)
    scale = ccfg.amplitude_scale()
    scale_vec = None
    if ccfg.geometry is not None:
        rel = chl.relative_gains(jax.random.fold_in(key, _GEOM_SALT),
                                 ccfg.geometry, cfg.num_devices)
        scale_vec = np.asarray(scale * rel, np.float64)
        scale = jnp.asarray(scale_vec, jnp.float32)
    h_jax, fad0 = model.init(ccfg, scale, key)
    h = np.asarray(h_jax, np.float64)
    fad_state = None if fad0 is None else np.asarray(fad0, np.float64)
    h_hat = h
    if ccfg.csi_error > 0.0:
        h_hat = np.asarray(chl.estimate(
            jnp.asarray(h, jnp.float32),
            jax.random.fold_in(key, _CSI_SALT), ccfg.csi_error, scale,
            ccfg.csi_error_model), np.float64)
    return h, h_hat, fad_state, scale_vec


def setup(cfg: FLConfig, params0: PyTree, model_dim: int) -> FLState:
    """Draw the radio environment and run the paper's parameter
    optimization.  Algorithm 1 (and the receiver-gain calibration) runs on
    the server's estimate ``h_hat`` — what the server can actually know —
    which is ``h`` itself under perfect CSI (``csi_error = 0``)."""
    h, h_hat, fad_state, scale_vec = _setup_channel(cfg)
    b_max = np.full(cfg.num_devices, cfg.channel.b_max)
    extra = dict(model_dim=model_dim, h_hat=h_hat, fad_state=fad_state,
                 scale=scale_vec,
                 client_state=clientlib.init_state(cfg.client, params0,
                                                   cfg.num_devices))

    if cfg.amplification == "bmax":
        b = b_max.copy()
        # comparison method of Fig. 1(a): same a * sum(h_hat b) as the
        # optimized run
        sol = amp.solve_problem3(h_hat, cfg.channel.noise_var, model_dim,
                                 b_max)
        if cfg.case == "I":
            s_opt = amp.optimal_S(sol.Z, cfg.smoothness_L, cfg.p, cfg.expected_loss_drop)
            a = 1.0 / (s_opt * float(np.sum(h_hat * sol.b)))
            a = a * float(np.sum(h_hat * sol.b)) / float(np.sum(h_hat * b))
            eta0 = 1.0
        else:
            c2 = amp.optimize_case2(h_hat, cfg.channel.noise_var, model_dim,
                                    b_max,
                                    cfg.smoothness_L, cfg.strong_convexity_M,
                                    cfg.grad_bound, cfg.theta_th,
                                    s=cfg.s_target, epsilon=cfg.epsilon_target)
            a_eta = c2.a_eta * float(np.sum(h_hat * c2.b)) / float(np.sum(h_hat * b))
            a, eta0 = a_eta / cfg.eta, cfg.eta
        return FLState(params0, h, b, a, eta0, **extra)

    if cfg.case == "I":
        c1 = amp.optimize_case1(h_hat, cfg.channel.noise_var, model_dim,
                                b_max,
                                cfg.smoothness_L, cfg.p, cfg.expected_loss_drop)
        return FLState(params0, h, c1.b, c1.a, 1.0, **extra)
    c2 = amp.optimize_case2(h_hat, cfg.channel.noise_var, model_dim, b_max,
                            cfg.smoothness_L, cfg.strong_convexity_M,
                            cfg.grad_bound, cfg.theta_th,
                            s=cfg.s_target, epsilon=cfg.epsilon_target)
    return FLState(params0, h, c2.b, c2.a_eta / cfg.eta, cfg.eta, **extra)


def _eta_t(cfg: FLConfig, eta0, t: jax.Array) -> jax.Array:
    if cfg.case == "I":
        return eta0 / jnp.maximum(t.astype(jnp.float32), 1.0) ** cfg.p
    return jnp.asarray(eta0, jnp.float32)


def _participation_mask(cfg: FLConfig, key, t) -> jax.Array:
    """[K] 0/1 per-round participation draw.  ``bernoulli`` masks each device
    independently with probability p; ``fixed`` schedules exactly
    ``round(p * K)`` devices uniformly at random."""
    mk = jax.random.fold_in(jax.random.fold_in(key, t), _MASK_SALT)
    k = cfg.num_devices
    if cfg.participation_mode == "bernoulli":
        return jax.random.bernoulli(mk, cfg.participation, (k,)).astype(
            jnp.float32)
    m = max(1, int(round(cfg.participation * k)))
    perm = jax.random.permutation(mk, k)
    return jnp.zeros((k,), jnp.float32).at[perm[:m]].set(1.0)


def _participation_mask_block(cfg: FLConfig, key, t, lo: int,
                              hi: int) -> jax.Array:
    """Lazy per-K-block participation draw for ``bernoulli`` mode: device
    ``i``'s coin folds from its own index, so any blocking of ``[0, K)``
    concatenates to the same mask — the 100k+-device path never materializes
    a [K] draw it won't use this block.  ``fixed`` mode needs the global
    permutation and has no lazy form (use ``active_gather`` there)."""
    if cfg.participation_mode != "bernoulli":
        raise ValueError("lazy per-block participation draws exist for "
                         "'bernoulli' only ('fixed' draws one global "
                         "permutation)")
    mk = jax.random.fold_in(jax.random.fold_in(key, t), _MASK_SALT)
    keys = jax.vmap(lambda i: jax.random.fold_in(mk, i))(jnp.arange(lo, hi))
    u = jax.vmap(lambda k_: jax.random.uniform(k_, ()))(keys)
    return (u < cfg.participation).astype(jnp.float32)


def _active_indices(cfg: FLConfig, key, t) -> jax.Array:
    """Sorted [m] indices of the round's fixed-mode participant set — the
    SAME permutation draw as ``_participation_mask``, so ``mask[idx] == 1``
    by construction, and ascending order keeps the gathered K-way sums in
    the dense path's reduction order (the bitwise-parity contract)."""
    mk = jax.random.fold_in(jax.random.fold_in(key, t), _MASK_SALT)
    m = max(1, int(round(cfg.participation * cfg.num_devices)))
    perm = jax.random.permutation(mk, cfg.num_devices)
    return jnp.sort(perm[:m])


# fences promoted to core.ota (the OTA-level sharded streaming path needs
# them too); the runtime names stay as aliases for their existing call sites
_fence_leaf = ota.fence_leaf
_fusion_fence = ota.fusion_fence


# Each stage of the round runs under a jax.named_scope (local_step,
# aggregate, server_update, fading_refresh), so its compiled ops carry the
# stage in their op_name metadata for device traces; the arithmetic is
# unchanged.
@jax.named_scope("local_step")
def _local_transmit(cfg: FLConfig, grad_fn: GradFn, params, batch,
                    corr=None, dev_state=None) -> PyTree:
    """The quantity each device hands to the scheme's transform: its local
    gradient for ``local_steps == 1`` (the paper), else the accumulated model
    delta of H local SGD steps, ``(w - w_k^H) / (H * local_lr)`` — the average
    local gradient along the trajectory, so its magnitude is comparable to a
    single gradient and ``grad_bound``-based schemes stay calibrated.

    ``corr(p, g, dev_state_k)`` is the client algorithm's local-objective
    correction (``repro.fl.clients``), applied to EVERY local gradient along
    the H-step trajectory; ``dev_state`` the stacked per-device state it
    reads (vmapped alongside the batch).  ``corr=None`` — the ``sgd``
    default — takes the pre-registry code path verbatim (bitwise)."""
    if corr is None:
        if cfg.local_steps == 1:
            return jax.vmap(lambda db: grad_fn(params, db))(batch)

        def one_device(db):
            def step(p, _):
                g = grad_fn(p, db)
                return jax.tree_util.tree_map(
                    lambda w, gg: w - jnp.asarray(cfg.local_lr, w.dtype)
                    * gg.astype(w.dtype), p, g), None

            p_h, _ = jax.lax.scan(step, params, None, length=cfg.local_steps)
            inv = 1.0 / (cfg.local_steps * cfg.local_lr)
            return jax.tree_util.tree_map(
                lambda w0, wh: (w0 - wh) * jnp.asarray(inv, w0.dtype),
                params, p_h)

        return jax.vmap(one_device)(batch)

    def local_grad(p, db, ds):
        return corr(p, grad_fn(p, db), ds)

    if cfg.local_steps == 1:
        if dev_state is None:
            return jax.vmap(lambda db: local_grad(params, db, None))(batch)
        return jax.vmap(lambda db, ds: local_grad(params, db, ds))(
            batch, dev_state)

    def one_device_corr(db, ds):
        def step(p, _):
            g = local_grad(p, db, ds)
            return jax.tree_util.tree_map(
                lambda w, gg: w - jnp.asarray(cfg.local_lr, w.dtype)
                * gg.astype(w.dtype), p, g), None

        p_h, _ = jax.lax.scan(step, params, None, length=cfg.local_steps)
        inv = 1.0 / (cfg.local_steps * cfg.local_lr)
        return jax.tree_util.tree_map(
            lambda w0, wh: (w0 - wh) * jnp.asarray(inv, w0.dtype),
            params, p_h)

    if dev_state is None:
        return jax.vmap(lambda db: one_device_corr(db, None))(batch)
    return jax.vmap(one_device_corr)(batch, dev_state)


def _round_math(cfg: FLConfig, sch, opt, grad_fn: GradFn, params, opt_state,
                batch, h, h_hat, b, a, eta0, t, key,
                over: Optional[BatchAxes] = None, client_state=None):
    """One FL round (local computation -> OTA aggregate(s) -> server
    optimizer step) plus the scalar diagnostics of ``DIAG_KEYS``.  Pure;
    traced identically by both drivers.  ``over`` carries the per-experiment
    traced scalars of a batched run (None — the single-experiment default —
    bakes the ``cfg`` values into the trace exactly as before).

    ``client_state`` is the client algorithm's state
    (``{"dev": [K, ...], "srv": ...}``, see ``repro.fl.clients``; None for
    stateless algorithms), threaded through the round alongside params:
    returns ``(params, opt_state, client_state, diag)``.  A multi-slot
    algorithm (scaffold) runs a SECOND OTA transmission after the gradient
    slot — its own normalization scheme, the same channel realization, an
    independent noise key (``_SLOT_SALT``), and its eq.-8 energy added to
    ``tx_energy``.

    ``h`` is the TRUE channel (the air superposes with it); ``h_hat`` the
    server's estimate — the participation rescale and the server-side
    post-transform run on ``h_hat`` (the server cannot know ``h``).  Under
    perfect CSI the caller passes ``h_hat=None``: the estimate aliases the
    SAME traced value as ``h``, so every CSI term collapses exactly (the
    ``csi_gain_err`` diagnostic is a hard 0, not a lowering residual)."""
    if h_hat is None:
        h_hat = h
    noise_var = cfg.channel.noise_var
    grad_bound = cfg.grad_bound
    if over is not None:
        if over.noise_var is not None:
            noise_var = over.noise_var
        if over.grad_bound is not None:
            grad_bound = over.grad_bound
    alg = clientlib.get(cfg.client.algo)
    cp = clientlib.resolve_params(
        cfg.client,
        over.client_mu if over is not None else None,
        over.client_alpha if over is not None else None)
    dev_state = client_state["dev"] if client_state is not None else None
    srv_state = client_state["srv"] if client_state is not None else None
    corr = None
    if alg.correction is not None:
        # w_round = params (the round's broadcast model); the closure is
        # traced inside the device vmap, p being the device-local weights
        corr = lambda p, g, ds: alg.correction(cp, p, params, ds, srv_state, g)
    if cfg.participation < 1.0:
        mask = _participation_mask(cfg, key, t)
        b_eff, a_eff = ota.participation_fold(h_hat, b, a, mask)
    else:
        mask = None
        b_eff, a_eff = b, a
    if cfg.active_gather:
        # fixed-mode active set: gather the scheduled participants' batches
        # BEFORE the local computation so gradient compute scales with
        # m = round(p K), then scatter the m gradients back into a zero
        # [K, ...] stack and run the UNCHANGED dense aggregation.  A masked
        # device's superposition / side-info / energy weight is an exact
        # zero either way (b_eff = 0, and 0 * x == 0 * 0 in every K-way
        # reduction term), so the round is bitwise the dense masked round —
        # the participants are just the only devices that ever run grad_fn.
        idx = _active_indices(cfg, key, t)  # tracelint: disable=TL002 mask and active-set draws fold in distinct salts inside the helpers; streams are disjoint by construction
        dev_active = (None if dev_state is None else
                      jax.tree_util.tree_map(lambda l: l[idx], dev_state))
        active = _local_transmit(
            cfg, grad_fn, params,
            jax.tree_util.tree_map(lambda l: l[idx], batch),
            corr, dev_active)
        stacked = jax.tree_util.tree_map(
            lambda l: jnp.zeros((cfg.num_devices,) + l.shape[1:],
                                l.dtype).at[idx].set(l), active)
        b_air = b_eff[idx]
    else:
        idx = None
        dev_active = dev_state
        active = stacked = _local_transmit(cfg, grad_fn, params, batch,
                                           corr, dev_active)
        b_air = b_eff
    with jax.named_scope("aggregate"):
        if mask is not None:
            # fence the gradient stack so the aggregation below consumes a
            # materialized [K, ...] value: without it XLA fuses the
            # aggregate's K-way reductions into the (round-shape-dependent)
            # gradient producer, and the dense-masked and active-gather
            # programs — whose reduction TERMS are identical, masked devices
            # contributing exact zeros — would associate them differently,
            # breaking the bitwise gather contract.  Full-participation
            # rounds (the golden-pinned default) never take this branch.
            stacked = _fusion_fence(stacked)
        if mask is not None and sch.baseline:
            # baseline schemes bypass the channel (plain mean on every
            # backend), so the mask cannot reach them through b_eff — average
            # over the participants only, or the ideal reference would
            # silently use all K devices while the diagnostics report a
            # partial cohort
            w = mask / jnp.maximum(jnp.sum(mask), 1.0)
            y = jax.tree_util.tree_map(
                lambda l: jnp.tensordot(w, l.astype(jnp.float32),
                                        axes=(0, 0)),
                stacked)
        else:
            ocfg = ota.OTAConfig(scheme=cfg.scheme, a=a_eff,
                                 noise_var=noise_var,
                                 grad_bound=grad_bound, backend=cfg.backend)
            y = ota.aggregate(ocfg, stacked, h, b_eff,
                              jax.random.fold_in(key, t), h_hat=h_hat)
        # one stats pass feeds BOTH diagnostics (grad norms and the eq. 8
        # transmit-energy accounting); the aggregate above keeps its own
        # internal stats — folding the two would need aggregate() to return
        # them.  Under active_gather the stats cover the participants only
        # (the scattered zero rows are channel inputs, not computed
        # gradients): the grad-norm diagnostics shrink to the active set,
        # while tx_energy is unchanged (masked devices spent nothing — their
        # dense energy terms were b_k = 0)
        stats = schemes.compute_stats(active, sch, batched=True)
        norms = jnp.sqrt(stats.sq_norm)
        tx = schemes.transmit_energy(sch, stats, b_air, grad_bound,
                                     None if idx is not None else mask)
        if idx is not None:
            # scatter the active set's energies back to the [K] layout
            # (masked devices spent exactly 0) and fence, so the eq.-8 total
            # below runs the same [K]-way sum as the dense masked round
            # (per-device energies can still carry ulp noise: [m]-row
            # reductions vectorize differently than [K]-row ones)
            tx = jnp.zeros((cfg.num_devices,), tx.dtype).at[idx].set(tx)
        if mask is not None:
            tx = _fusion_fence(tx)
        # total transmit energy sum_k b_k^2 ||x_k||^2 (eq. 8 budget) via the
        # scheme's analytic accounting; masked-out devices spend nothing.  A
        # second OTA slot adds its own eq.-8 term below.
        tx_energy = jnp.sum(tx)

        new_client_state = client_state
        if alg.stateful:
            tmap = jax.tree_util.tree_map
            hlr = cfg.local_steps * cfg.local_lr
            dev_new = dev_state
            dev_new_active = dev_active
            if alg.has_state:
                # the state transition sees the round's transmitted
                # statistic (``active``: grad for H = 1, the accumulated
                # delta otherwise)
                dev_new_active = alg.update_state(cp, hlr, dev_active,
                                                  srv_state, active)
                if idx is not None:
                    dev_new = tmap(lambda full, act: full.at[idx].set(act),
                                   dev_state, dev_new_active)
                elif mask is not None:
                    # a masked device did not participate: its state must
                    # not move (the raw transition still feeds slot 2 below,
                    # where b_eff = 0 already silences the masked rows)
                    keep = mask.astype(bool)
                    dev_new = tmap(
                        lambda new, old: jnp.where(
                            keep.reshape((-1,) + (1,) * (new.ndim - 1)),
                            new, old), dev_new_active, dev_state)
                else:
                    dev_new = dev_new_active
            srv_new = srv_state
            if alg.num_slots == 2:
                # ---- the second OTA transmission slot ---------------------
                # same channel realization h/b_eff/a_eff (the slots are
                # consecutive symbols of one coherence block), its own
                # normalization scheme, an independent noise draw, and its
                # own eq.-8 energy.  The server learns its state from the
                # DE-GAINED aggregate: y2 / (a sum h_hat b) is approximately
                # the participant-mean transmitted statistic.
                sch2 = schemes.get(cfg.client.variate_scheme)
                x2_active = alg.variate_stat(cp, dev_active, dev_new_active,
                                             srv_state, active)
                if idx is not None:
                    x2 = tmap(lambda l: jnp.zeros(
                        (cfg.num_devices,) + l.shape[1:],
                        l.dtype).at[idx].set(l), x2_active)
                else:
                    x2 = x2_active
                if mask is not None:
                    x2 = _fusion_fence(x2)
                stats2 = schemes.compute_stats(x2_active, sch2, batched=True)
                tx2 = schemes.transmit_energy(
                    sch2, stats2, b_air, grad_bound,
                    None if idx is not None else mask)
                if idx is not None:
                    tx2 = jnp.zeros((cfg.num_devices,),
                                    tx2.dtype).at[idx].set(tx2)
                if mask is not None:
                    tx2 = _fusion_fence(tx2)
                tx_energy = tx_energy + jnp.sum(tx2)
                ocfg2 = ota.OTAConfig(scheme=cfg.client.variate_scheme,
                                      a=a_eff, noise_var=noise_var,
                                      grad_bound=grad_bound,
                                      backend=cfg.backend)
                key2 = jax.random.fold_in(jax.random.fold_in(key, t),
                                          _SLOT_SALT)
                y2 = ota.aggregate(ocfg2, x2, h, b_eff, key2, h_hat=h_hat)
                gain = a_eff * jnp.sum(h_hat * b_eff)
                y2_hat = tmap(
                    lambda l: l / jnp.maximum(gain, schemes.EPS), y2)
                # |participants|/K scales the server variate step (SCAFFOLD's
                # m/K); an empty round has gain = 0 AND frac = 0 — srv holds
                frac = (jnp.sum(mask) / cfg.num_devices if mask is not None
                        else jnp.asarray(1.0, jnp.float32))
                srv_new = alg.apply_variate(cp, srv_state, y2_hat, frac)
            new_client_state = {"dev": dev_new, "srv": srv_new}

    diag_core = {
        "grad_norm_mean": jnp.mean(norms),
        "grad_norm_min": jnp.min(norms),
        "grad_norm_max": jnp.max(norms),
        "tx_energy": tx_energy,
    }
    new_params, new_opt_state, diag = _round_tail(
        cfg, sch, opt, params, opt_state, y, mask, eta0, t, diag_core, a_eff,
        h, h_hat, b_eff)
    return new_params, new_opt_state, new_client_state, diag


@jax.named_scope("server_update")
def _round_tail(cfg, sch, opt, params, opt_state, y, mask, eta0, t,
                diag_core, a_eff, h, h_hat, b_eff):
    """Post-aggregation tail shared by the dense and streaming rounds:
    empty-round gating, the server-optimizer step, and the ``DIAG_KEYS``
    assembly.  ``diag_core`` carries the grad-norm/energy numbers, which the
    two rounds compute differently (one dense stats pass vs a blocked
    running reduction); everything here sees only full-[K] channel vectors
    and the round's update direction, so it is layout-agnostic."""
    if mask is not None:
        # an empty round (possible under bernoulli draws) applies no update:
        # participation_fold zeroed the gain, but server_post schemes can
        # re-shift y, so the update direction is gated too
        any_part = (jnp.sum(mask) > 0).astype(jnp.float32)
        y = jax.tree_util.tree_map(
            lambda l: l * any_part.astype(l.dtype), y)
    eta = _eta_t(cfg, eta0, t)
    new_params, new_opt_state = opt.update(y, opt_state, params, lr=eta)
    if mask is not None:
        # ...and so is the state transition itself: a stateful server
        # optimizer (momentum / adam moments, even weight decay) must not
        # move the model or its moments on a round nobody transmitted in
        keep = jnp.sum(mask) > 0
        new_params = jax.tree_util.tree_map(
            lambda n, o: jnp.where(keep, n, o), new_params, params)
        new_opt_state = jax.tree_util.tree_map(
            lambda n, o: jnp.where(keep, n, o), new_opt_state, opt_state)
    # sharded rounds pin the real-valued [K]/[N] reductions below so the
    # diagnostics stay bitwise across the shard_map / emulated programs
    # (see _round_math_streaming); mask sums are 0/1-exact and stay plain
    ksum = (ota.pinned_sum
            if cfg.device_mesh is not None and cfg.device_mesh > 1
            else jnp.sum)
    if sch.baseline:
        # the ideal reference bypasses the channel; no gain to misalign
        csi_gain_err = jnp.zeros((), jnp.float32)
    else:
        # relative effective-gain misalignment: the air realizes
        # a sum h_k b_k, the server designed a sum h_hat_k b_k.  Computed
        # through the DIFFERENCE (h - h_hat) so equal estimates give a hard
        # 0 (two independently-lowered sums would leave an ulp residual)
        designed = a_eff * ksum(h_hat * b_eff)
        gap = a_eff * ksum((h - h_hat) * b_eff)
        csi_gain_err = (gap / jnp.maximum(jnp.abs(designed),
                                          schemes.EPS)).astype(jnp.float32)
    diag = {
        **diag_core,
        "eta": eta,
        "update_norm": jnp.sqrt(sum(ksum(jnp.square(l))
                                    for l in jax.tree_util.tree_leaves(y))),
        "num_participants": (jnp.sum(mask) if mask is not None
                             else jnp.asarray(float(cfg.num_devices),
                                              jnp.float32)),
        "csi_gain_err": csi_gain_err,
    }
    return new_params, new_opt_state, diag


def _combine_shard_carries(stacked):
    """Close the sharded streaming round: fold D stacked per-shard scan
    carries ``(ota_carry, norm_sum, norm_min, norm_max, tx_sum[, ota_carry_2])``
    into one.  Every accumulator field reduces through the deterministic
    left fold of ``distribution.ota_collectives.fold_shards`` — the ONE
    combine both execution paths (shard_map and the emulated outer scan)
    share, which is what makes them bitwise-identical; the min/max
    diagnostics fold with their own (order-free) ops on the same path."""
    from repro.distribution import ota_collectives as coll
    out = (coll.fold_shards(stacked[0]),
           coll.fold_shards(stacked[1]),
           coll.fold_shards(stacked[2], jax.lax.min),
           coll.fold_shards(stacked[3], jax.lax.max),
           coll.fold_shards(stacked[4]))
    if len(stacked) > 5:
        out = out + (coll.fold_shards(stacked[5]),)
    return out


def _scan_stream_blocks(cfg: FLConfig, body, carry0, xs):
    """Drive the streaming round's block scan, sharded when
    ``cfg.device_mesh`` asks for it.

    Plain (``device_mesh`` None/1): one ``lax.scan`` over all nb blocks —
    the PR-6 flat left fold, bitwise-pinned.

    Sharded (``device_mesh = D``): the [nb, k_block, ...] xs leaves become
    [D, nb/D, k_block, ...]; each shard left-folds its contiguous run of
    blocks from the same zero carry, and ``_combine_shard_carries`` closes
    the round.  On a physical mesh the per-shard folds run SPMD under
    ``shard_map`` (params and the other closed-over round state replicate;
    the xs split; ONE ``all_gather`` of the partial carries is the round's
    only cross-shard collective); otherwise an outer ``lax.scan`` emulates
    the shards.  Both paths run the SAME blocking and the SAME combine, so
    the choice is invisible in the trajectory (bitwise).

    Returns ``(combined_carry, ys)`` with ``ys`` in the flat [nb, ...]
    block order either way."""
    if cfg.device_mesh is None or cfg.device_mesh <= 1:
        return jax.lax.scan(body, carry0, xs)
    from jax.sharding import PartitionSpec as P

    from repro.distribution import ota_collectives as coll
    from repro.distribution import sharding as shardlib

    d = cfg.device_mesh
    tmap = jax.tree_util.tree_map
    xs_sh = tmap(lambda l: l.reshape((d, l.shape[0] // d) + l.shape[1:]), xs)

    def shard_fold(xs_shard):
        return jax.lax.scan(body, carry0, xs_shard)

    mesh = shardlib.device_mesh(d)
    if mesh is None:
        _, (stacked, ys_sh) = jax.lax.scan(
            lambda _, xs_s: (None, shard_fold(xs_s)), None, xs_sh)
        ys = tmap(lambda l: l.reshape((l.shape[0] * l.shape[1],)
                                      + l.shape[2:]), ys_sh)
    else:
        axis = shardlib.FL_DEVICE_AXIS

        def per_shard(xs_s):
            local = tmap(lambda l: l[0], xs_s)
            carry, ys_local = shard_fold(local)
            return coll.gather_shards(carry, axis), ys_local

        # replicated constraints at BOTH shard_map boundaries: without them
        # GSPMD propagates the manual axis sharding backward into the xs
        # producers and forward through ys into the next round's carry, and
        # the surrounding round math (channel refresh, Problem-3 solve,
        # _round_tail) compiles 4-way-partitioned in the physical program
        # only — which drifts from the emulated program by ulps.  The
        # constraints change placement, never values.
        rep = jax.sharding.NamedSharding(mesh, P())
        xs_sh = tmap(lambda l: jax.lax.with_sharding_constraint(l, rep),
                     xs_sh)
        stacked, ys = jax.shard_map(
            per_shard, mesh=mesh,
            in_specs=(tmap(lambda _: P(axis), xs_sh),),
            out_specs=(P(), P(axis)),
            axis_names={axis}, check_vma=False)(xs_sh)
        stacked = tmap(lambda l: jax.lax.with_sharding_constraint(l, rep),
                       stacked)
        ys = tmap(lambda l: jax.lax.with_sharding_constraint(l, rep), ys)
    # fence the combined carry: the consumers (streaming_finish, the
    # server-state fold) must compile independently of whether the partials
    # arrived through shard_map or the emulated scan, or their producer
    # fusion drifts by ulps between the two paths
    return _fusion_fence(_combine_shard_carries(stacked)), ys


def _round_math_streaming(cfg: FLConfig, sch, opt, grad_fn: GradFn, params,
                          opt_state, batch, h, h_hat, b, a, eta0, t, key,
                          over: Optional[BatchAxes] = None,
                          block_batch_fn=None, client_state=None):
    """The flat-memory round (``cfg.k_block``): local gradients are computed
    and folded into the OTA accumulator ``k_block`` devices at a time through
    the streaming carry API (``ota.streaming_carry/_block/_finish``) inside a
    ``lax.scan`` over K-blocks — the [K, ...] transmit stack never exists, so
    the round's working set is O(k_block * N) plus O(K) channel vectors.

    ``batch`` is the dense per-device batch pytree over the streamed axis
    (the active set under ``active_gather``, else all K), or ``None`` — then
    ``block_batch_fn(t, dev_idx)`` materializes one block's [k_block, ...]
    batches from its [k_block] device indices, the 100k-device path where
    even a round's batch stack would not fit.

    Parity with the dense round: every per-device term (grad, scale, energy)
    is computed identically; the K-way sums re-associate into block partials
    (documented-ulp, tests/test_streaming.py), the channel-noise draw is
    bitwise-shared, and grad_norm_min/max are exact (min/max associate).

    ``client_state`` threads the client algorithm's state exactly like the
    dense round (returns a 4-tuple): the per-device ``[K, ...]`` stack rides
    the block scan's ``xs`` (its working set is O(k_block * N) per leaf),
    updated states come back as the scan's per-block outputs, and a second
    OTA slot folds into its OWN streaming accumulator alongside slot 1's.

    ``cfg.device_mesh`` partitions the block scan over mesh shards
    (``_scan_stream_blocks``): params/opt/server-state replicate, the
    blocked channel / participation / ``active_gather`` index vectors (and
    the slot-2 client-state stacks) shard with the blocks, and both OTA
    slots' accumulators close through one deterministic cross-shard fold
    before ``streaming_finish`` draws the (bitwise-shared) noise once."""
    if h_hat is None:
        h_hat = h
    noise_var = cfg.channel.noise_var
    grad_bound = cfg.grad_bound
    if over is not None:
        if over.noise_var is not None:
            noise_var = over.noise_var
        if over.grad_bound is not None:
            grad_bound = over.grad_bound
    alg = clientlib.get(cfg.client.algo)
    cp = clientlib.resolve_params(
        cfg.client,
        over.client_mu if over is not None else None,
        over.client_alpha if over is not None else None)
    dev_state = client_state["dev"] if client_state is not None else None
    srv_state = client_state["srv"] if client_state is not None else None
    corr = None
    if alg.correction is not None:
        corr = lambda p, g, ds: alg.correction(cp, p, params, ds, srv_state, g)
    # Under device_mesh the round's out-of-scan [K]-way REAL reductions
    # (effective-gain sums) are pinned (ota.pinned_sum): the shard_map and
    # emulated programs surround them with different computations, and an
    # unpinned jnp.sum lets XLA cluster each one differently — a 1-ulp gain
    # drift that compounds over rounds.  Sums of 0/1 masks are exact under
    # any association and stay plain.
    fence = cfg.device_mesh is not None and cfg.device_mesh > 1
    ksum = ota.pinned_sum if fence else jnp.sum
    if cfg.participation < 1.0:
        mask = _participation_mask(cfg, key, t)
        b_eff, a_eff = ota.participation_fold(h_hat, b, a, mask, sum_fn=ksum)
    else:
        mask = None
        b_eff, a_eff = b, a
    if cfg.active_gather:
        idx = _active_indices(cfg, key, t)  # tracelint: disable=TL002 same salt discipline as the dense round: helpers fold_in _MASK_SALT vs the gather salt
        if batch is not None:
            batch = jax.tree_util.tree_map(lambda l: l[idx], batch)
        h_air, h_srv, b_air = h[idx], h_hat[idx], b_eff[idx]
        dev = idx
    else:
        idx = None
        h_air, h_srv, b_air = h, h_hat, b_eff
        dev = jnp.arange(cfg.num_devices)
    s = cfg.stream_length()
    kb = min(cfg.k_block, s)
    nb = s // kb

    def blk(v):
        return v.reshape((nb, kb) + v.shape[1:])

    xs = {"ha": blk((h_air * b_air).astype(jnp.float32)),
          "hs": blk((h_srv * b_air).astype(jnp.float32)),
          "b": blk(b_air), "dev": blk(dev)}
    if dev_state is not None:
        # one K-block of per-device state per scan step: gathered to the
        # active set first (like the batches), then blocked like everything
        # on the streamed axis
        dev_str = (dev_state if idx is None else
                   jax.tree_util.tree_map(lambda l: l[idx], dev_state))
        xs["cst"] = jax.tree_util.tree_map(blk, dev_str)
    if mask is not None and idx is None:
        xs["mask"] = blk(mask)
    weighted = mask is not None and sch.baseline
    if weighted:
        # masked baseline: the participant mean, accumulated as the SAME
        # hb-free weighted sum the dense round takes (see _round_math)
        w = mask / jnp.maximum(jnp.sum(mask), 1.0)
        xs["w"] = blk(w if idx is None else w[idx])
    if batch is not None:
        xs["batch"] = jax.tree_util.tree_map(blk, batch)
    elif block_batch_fn is None:
        raise ValueError("streaming round got batch=None and no "
                         "block_batch_fn — pass run(..., "
                         "block_batch_provider=...) for the lazy-batch path")
    ocfg = ota.OTAConfig(scheme=cfg.scheme, a=a_eff, noise_var=noise_var,
                         grad_bound=grad_bound, backend=cfg.backend,
                         k_block=kb)
    template = jax.tree_util.tree_map(
        lambda p: jnp.zeros(p.shape, jnp.float32), params)
    zero = jnp.zeros((), jnp.float32)
    tmap = jax.tree_util.tree_map
    hlr = cfg.local_steps * cfg.local_lr
    two_slot = alg.num_slots == 2
    sch2 = ocfg2 = None
    if two_slot:
        # the second slot accumulates into its OWN streaming carry,
        # interleaved block-by-block with slot 1's
        sch2 = schemes.get(cfg.client.variate_scheme)
        ocfg2 = ota.OTAConfig(scheme=cfg.client.variate_scheme, a=a_eff,
                              noise_var=noise_var, grad_bound=grad_bound,
                              backend=cfg.backend, k_block=kb)
    carry0 = (ota.streaming_carry(ocfg, template), zero,
              jnp.asarray(jnp.inf, jnp.float32),
              jnp.asarray(-jnp.inf, jnp.float32), zero)
    if two_slot:
        carry0 = carry0 + (ota.streaming_carry(ocfg2, template),)

    # Under device_mesh the same block math lowers in two contexts
    # (shard_map's manual body and the emulated outer scan); fencing the
    # transmit quantities pins their values before the blocked reductions,
    # so XLA's producer fusion cannot differ between the contexts and the
    # bitwise phys==emulated contract holds for every scheme/algorithm.
    # The plain stream stays unfenced (its lowering is bitwise-pinned).
    def body(carry, x):
        oc, nsum, nmin, nmax, txsum = carry[:5]
        bat = x["batch"] if "batch" in x else block_batch_fn(t, x["dev"])
        g_blk = _local_transmit(cfg, grad_fn, params, bat, corr,
                                x.get("cst"))
        if fence:
            g_blk = _fusion_fence(g_blk)
        stats = schemes.compute_stats(g_blk, sch, batched=True)
        norms = jnp.sqrt(stats.sq_norm)
        tx = schemes.transmit_energy(sch, stats, x["b"], grad_bound,
                                     x.get("mask"))
        oc = ota.streaming_block(ocfg, oc, g_blk, x["ha"], x["hs"],
                                 stats=stats, grad_bound=grad_bound,
                                 baseline_weights=x.get("w"))
        txsum = txsum + jnp.sum(tx)
        ys = None
        cst = x.get("cst")
        raw_new = cst
        if alg.has_state:
            raw_new = alg.update_state(cp, hlr, cst, srv_state, g_blk)
            if "mask" in x:
                # masked devices hold their state (the raw transition still
                # feeds slot 2, where b_eff = 0 silences those rows)
                keep = x["mask"].astype(bool)
                ys = tmap(lambda new, old: jnp.where(
                    keep.reshape((-1,) + (1,) * (new.ndim - 1)), new, old),
                    raw_new, cst)
            else:
                ys = raw_new
        new_carry = (oc, nsum + jnp.sum(norms),
                     jnp.minimum(nmin, jnp.min(norms)),
                     jnp.maximum(nmax, jnp.max(norms)), txsum)
        if two_slot:
            x2_blk = alg.variate_stat(cp, cst, raw_new, srv_state, g_blk)
            if fence:
                x2_blk = _fusion_fence(x2_blk)
            stats2 = schemes.compute_stats(x2_blk, sch2, batched=True)
            tx2 = schemes.transmit_energy(sch2, stats2, x["b"], grad_bound,
                                          x.get("mask"))
            oc2 = ota.streaming_block(ocfg2, carry[5], x2_blk, x["ha"],
                                      x["hs"], stats=stats2,
                                      grad_bound=grad_bound)
            new_carry = new_carry[:4] + (txsum + jnp.sum(tx2), oc2)
        return new_carry, ys

    with jax.named_scope("aggregate"):
        carry_out, ys_out = _scan_stream_blocks(cfg, body, carry0, xs)
        oc, nsum, nmin, nmax, txsum = carry_out[:5]
        y = ota.streaming_finish(ocfg, oc, template, a_eff,
                                 jax.random.fold_in(key, t),
                                 noise_var=noise_var,
                                 num_devices=1.0 if weighted else float(s))
        new_client_state = client_state
        if alg.stateful:
            dev_new = dev_state
            if alg.has_state:
                flat_new = tmap(lambda l: l.reshape((s,) + l.shape[2:]),
                                ys_out)
                if idx is not None:
                    dev_new = tmap(lambda full, fl: full.at[idx].set(fl),
                                   dev_state, flat_new)
                else:
                    dev_new = flat_new
            srv_new = srv_state
            if two_slot:
                key2 = jax.random.fold_in(jax.random.fold_in(key, t),
                                          _SLOT_SALT)
                y2 = ota.streaming_finish(ocfg2, carry_out[5], template,
                                          a_eff, key2, noise_var=noise_var,
                                          num_devices=float(s))
                gain = a_eff * ksum(h_hat * b_eff)
                y2_hat = tmap(
                    lambda l: l / jnp.maximum(gain, schemes.EPS), y2)
                frac = (jnp.sum(mask) / cfg.num_devices if mask is not None
                        else jnp.asarray(1.0, jnp.float32))
                srv_new = alg.apply_variate(cp, srv_state, y2_hat, frac)
            new_client_state = {"dev": dev_new, "srv": srv_new}

    diag_core = {
        "grad_norm_mean": nsum / s,
        "grad_norm_min": nmin,
        "grad_norm_max": nmax,
        "tx_energy": txsum,
    }
    new_params, new_opt_state, diag = _round_tail(
        cfg, sch, opt, params, opt_state, y, mask, eta0, t, diag_core, a_eff,
        h, h_hat, b_eff)
    return new_params, new_opt_state, new_client_state, diag


@jax.named_scope("fading_refresh")
def _fading_refresh(cfg: FLConfig, model_dim: int, eff_gain, chan_key, t,
                    fad_state, over: Optional[BatchAxes] = None):
    """Time-varying channel (beyond the paper, which holds h_k fixed): step
    the configured fading model to the round-t channel, form the server's
    CSI estimate ``h_hat_t``, and RE-RUN the Problem-3 optimization on
    ``h_hat_t``, entirely in JAX — Algorithm 1 is cheap
    (O(log(1/eps)(K+1)^3)) relative to a round of local training, and
    ``solve_problem3_jax`` makes it scan-safe (and vmap-safe, which is how a
    batched run re-optimizes every experiment's b_t in one program).  The
    effective receiver-side gain a*sum(h_hat_k b_k) — what the server can
    design for, and what the bounds see — is held at its optimized value;
    under imperfect CSI the AIR still applies a*sum(h_k b_k), and the gap
    is the ``csi_gain_err`` diagnostic.

    ``fad_state`` is the persistent process state (AR(1) I/Q pair; None for
    stateless models); returns ``(h, h_hat, b, a, fad_state)``."""
    ccfg = cfg.channel
    model = chl.get(ccfg.model)
    noise_var = ccfg.noise_var
    b_max = ccfg.b_max
    rho = ccfg.rho
    csi_error = ccfg.csi_error
    scale = None
    if over is not None:
        if over.noise_var is not None:
            noise_var = over.noise_var
        if over.b_max is not None:
            b_max = over.b_max
        if over.rho is not None:
            rho = over.rho
        if over.csi_error is not None:
            csi_error = over.csi_error
        scale = over.rayleigh_scale
    if scale is None:
        scale = ccfg.amplitude_scale()
    h, fad_state = model.step(ccfg, scale,
                              jax.random.fold_in(chan_key, t), fad_state,
                              rho)
    h = h.astype(jnp.float32)
    h_hat = h
    if schemes.maybe_positive(csi_error):
        # maybe_positive: a traced csi_error (the batched sweep axis) must
        # resolve the branch at trace time; estimation with a concrete-zero
        # magnitude is exact (h_hat == h bitwise), so the gate is
        # value-preserving either way
        ck = jax.random.fold_in(jax.random.fold_in(chan_key, _CSI_SALT), t)
        h_hat = chl.estimate(h, ck, csi_error, scale,
                             ccfg.csi_error_model).astype(jnp.float32)
    if cfg.amplification == "optimal":
        sol = amp.solve_problem3_jax(h_hat, noise_var, model_dim, b_max)
        b = sol.b.astype(jnp.float32)
    else:
        b = jnp.broadcast_to(jnp.asarray(b_max, jnp.float32), h.shape)
    # pinned under device_mesh: a feeds every transmit scale, so a 1-ulp
    # clustering difference here would break the phys==emulated contract
    ksum = (ota.pinned_sum
            if cfg.device_mesh is not None and cfg.device_mesh > 1
            else jnp.sum)
    a = (eff_gain / ksum(h_hat * b)).astype(jnp.float32)
    return h, h_hat, b, a, fad_state


@_engine_cache
def _make_fading_refresh(cfg: FLConfig, model_dim: int):
    """Jitted per-round channel/Problem-3 refresh for the python driver
    (the scan driver inlines ``_fading_refresh`` in its scan body)."""
    def refresh(eff_gain, chan_key, t, fad_state, over):
        _count_trace("fading_refresh")
        return _fading_refresh(cfg, model_dim, eff_gain, chan_key, t,
                               fad_state, over)

    return jax.jit(refresh)


@_engine_cache
def make_round_step(cfg: FLConfig, grad_fn: GradFn, block_batch_fn=None):
    """Builds the jitted one-round function (the ``python`` driver's unit).

    round_step(params, opt_state, client_state, device_batches, h, h_hat, b,
               a, eta0, t, key)
        -> (new_params, new_opt_state, new_client_state, diagnostics)
    device_batches: pytree with leading [K, ...] axis (per-device
    minibatches) — or None under ``cfg.k_block`` with a ``block_batch_fn``
    (the lazy-batch streaming round; see ``_round_math_streaming``).
    client_state: the client algorithm's state dict (None for stateless
    algorithms — the pre-registry carry, bitwise).

    Cached on (cfg, grad_fn) — ``FLConfig`` is a frozen dataclass and
    functions/bound methods hash stably — so repeated ``run`` calls (resume,
    benchmark sweeps) reuse the compiled executable instead of re-tracing.
    """
    sch = schemes.get(cfg.scheme)
    opt = server_optimizer(cfg)

    @jax.jit
    def round_step(params, opt_state, client_state, device_batches, h, h_hat,
                   b, a, eta0, t, key):
        _count_trace("round_step")
        if cfg.k_block is not None:
            return _round_math_streaming(cfg, sch, opt, grad_fn, params,
                                         opt_state, device_batches, h, h_hat,
                                         b, a, eta0, t, key,
                                         block_batch_fn=block_batch_fn,
                                         client_state=client_state)
        return _round_math(cfg, sch, opt, grad_fn, params, opt_state,
                           device_batches, h, h_hat, b, a, eta0, t, key,
                           client_state=client_state)

    return round_step


def _make_chunk_scan(cfg: FLConfig, grad_fn: GradFn, model_dim: int,
                     trace_counter: str, block_batch_fn=None):
    """The one chunk-scan body BOTH engine builders share: ``lax.scan`` of
    ``_round_math`` (+ the block-fading refresh) over a chunk of rounds.
    ``over=None`` bakes the config numerics into the trace (the
    single-experiment engine); a ``BatchAxes`` of traced scalars is the
    vmapped sweep engine's per-experiment lane.  The carry threads the true
    channel ``h``, the server estimate ``h_hat``, and the fading-process
    state (None for stateless models — no carry leaf, so default traces are
    untouched)."""
    if trace_counter not in TRACE_KINDS:
        raise ValueError(f"trace_counter {trace_counter!r} not in TRACE_KINDS")
    sch = schemes.get(cfg.scheme)
    opt = server_optimizer(cfg)
    time_varying = cfg.channel.time_varying()

    def run_one(params, opt_state, client_state, h, h_hat, b, a, eta0, key,
                chan_key, eff_gain, fad_state, over, ts, batches):
        _count_trace(trace_counter)

        def body(carry, xs):
            params, opt_state, client_state, h, h_hat, b, a, fad_state = carry
            t, batch = xs
            if time_varying:
                h, h_hat_t, b, a, fad_state = _fading_refresh(
                    cfg, model_dim, eff_gain, chan_key, t, fad_state, over)
                # perfect-CSI runs arrive with h_hat=None and keep the carry
                # leafless: the refreshed estimate IS h there (the refresh's
                # csi gate was off), so nothing is lost by dropping it
                h_hat = None if h_hat is None else h_hat_t
            if cfg.k_block is not None:
                params, opt_state, client_state, diag = _round_math_streaming(
                    cfg, sch, opt, grad_fn, params, opt_state, batch,
                    h, h_hat, b, a, eta0, t, key, over,
                    block_batch_fn=block_batch_fn, client_state=client_state)
            else:
                params, opt_state, client_state, diag = _round_math(
                    cfg, sch, opt, grad_fn, params, opt_state, batch,
                    h, h_hat, b, a, eta0, t, key, over,
                    client_state=client_state)
            return (params, opt_state, client_state, h, h_hat, b, a,
                    fad_state), diag

        (params, opt_state, client_state, h, h_hat, b, a, fad_state), hist = \
            jax.lax.scan(
                body,
                (params, opt_state, client_state, h, h_hat, b, a, fad_state),
                (ts, batches))
        return params, opt_state, client_state, h, h_hat, b, a, fad_state, \
            hist

    return run_one


@_engine_cache
def _make_run_chunk(cfg: FLConfig, grad_fn: GradFn, model_dim: int,
                    block_batch_fn=None):
    """Builds the compiled multi-round engine: one ``lax.scan`` over a chunk
    of rounds.  Param and server-optimizer buffers are donated (in-place
    across chunks) and the per-round diagnostics come back as [chunk] device
    arrays — one host transfer per chunk, not one per round.  Cached like
    ``make_round_step``.
    """
    run_one = _make_chunk_scan(cfg, grad_fn, model_dim, "run_chunk",
                               block_batch_fn)

    def run_chunk(params, opt_state, client_state, h, h_hat, b, a, eta0,
                  key, chan_key, eff_gain, fad_state, over, ts, batches):
        return run_one(params, opt_state, client_state, h, h_hat, b, a,
                       eta0, key, chan_key, eff_gain, fad_state, over, ts,
                       batches)

    return jax.jit(run_chunk, donate_argnums=(0, 1, 2))


@_engine_cache
def _make_run_chunk_batched(cfg: FLConfig, grad_fn: GradFn, model_dim: int):
    """The vectorized sweep engine's unit: the SAME chunk scan as
    ``_make_run_chunk`` (one shared ``_make_chunk_scan`` body), wrapped in
    ``jax.vmap`` over a leading experiment axis E.  Per-experiment state
    (params, optimizer moments, channel h/b/a, eta0, PRNG keys, the
    ``BatchAxes`` traced numerics) is batched; the round schedule ``ts`` and
    the device batches are shared across experiments (in_axes=None), so a
    sub-batch that shares a task shares one host->device batch transfer per
    chunk.

    ``cfg`` must be the *structural* representative of the sub-batch
    (``structural_config``): every per-experiment numeric arrives through the
    batched inputs, never through the baked config, so all sub-batches with
    one structure share this cache entry AND its compiled executables.
    Block-fading chunks redraw every experiment's channel and re-run the
    Problem-3 bisection (``amp.solve_problem3_jax``) inside the vmapped scan
    — ``lax.while_loop``'s batching rule freezes converged lanes, so each
    lane's bisection is identical to its solo run."""
    run_one = _make_chunk_scan(cfg, grad_fn, model_dim, "run_chunk_batched")
    batched = jax.vmap(run_one, in_axes=(0,) * 13 + (None, None))
    return jax.jit(batched, donate_argnums=(0, 1, 2))


# name -> lru-cached builder, for cache_info()/clear_compile_caches()
_CACHED_BUILDERS = {
    "round_step": make_round_step,
    "run_chunk": _make_run_chunk,
    "run_chunk_batched": _make_run_chunk_batched,
    "fading_refresh": _make_fading_refresh,
}


def _plan_chunks(t0: int, num_rounds: int, eval_every: Optional[int],
                 chunk_size: int) -> List[List[int]]:
    """Group rounds ``t0+1 .. t0+num_rounds`` into scan chunks.  Every round
    the python driver would eval on (t == 1 or t % eval_every == 0) ends a
    chunk, so the scan driver observes params at identical rounds."""
    chunks: List[List[int]] = []
    cur: List[int] = []
    for t in range(t0 + 1, t0 + num_rounds + 1):
        cur.append(t)
        if (len(cur) >= chunk_size
                or (eval_every is not None
                    and (t == 1 or t % eval_every == 0))):
            chunks.append(cur)
            cur = []
    if cur:
        chunks.append(cur)
    return chunks


def _stack_batches(batch_provider, ts: Sequence[int]) -> PyTree:
    """One [chunk, K, ...] stacked batch pytree per chunk (a single host ->
    device transfer feeds the whole scan)."""
    per_round = [batch_provider(t) for t in ts]
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *per_round)


def _locked_eval_keys(metrics: Dict[str, float],
                      eval_keys: Optional[Tuple[str, ...]], t,
                      where: str = "") -> Tuple[str, ...]:
    """The metric key set is LOCKED on the first eval: an eval_fn that
    returns a key only on some rounds (or, batched, some experiments) would
    silently misalign that metric's history with hist['eval_round'].  Both
    ``run`` and ``run_batched`` share this contract."""
    if eval_keys is None:
        return tuple(metrics)
    if set(metrics) != set(eval_keys):
        raise ValueError(
            f"eval_fn returned metric keys {sorted(metrics)} at round "
            f"{t}{where}, but the history locked {sorted(eval_keys)} on the "
            "first eval — per-round metric lists must stay aligned with "
            "hist['eval_round']")
    return eval_keys


def run(cfg: FLConfig, state: FLState, grad_fn: GradFn,
        batch_provider: Callable[[int], Any], num_rounds: int,
        eval_fn: Optional[Callable[[PyTree], Dict[str, float]]] = None,
        eval_every: int = 10, *, driver: str = "scan",
        chunk_size: int = 16,
        chunk_batch_provider: Optional[Callable[[Sequence[int]], Any]] = None,
        block_batch_provider: Optional[Callable[[Any, Any], Any]] = None,
        recorder: Optional[Any] = None,
        ) -> Tuple[FLState, Dict[str, List]]:
    """Run ``num_rounds`` FL rounds on the selected driver.

    ``batch_provider(t)`` returns the per-device minibatch pytree (leading K
    axis) for round t.  ``driver='scan'`` (default) runs the compiled chunked
    engine; ``driver='python'`` the per-round host loop (see module
    docstring).  Both evaluate ``eval_fn`` at t == 1 and every
    ``eval_every``-th round, produce the same history keys, and persist the
    final channel state (h, h_hat, b, a under a time-varying channel, plus
    any fading-process state) and the round counter
    back into ``state`` so a second ``run`` resumes seamlessly.

    ``chunk_batch_provider(ts)``, when given, supplies a whole chunk's
    batches as one [T, K, ...] pytree (a single gather + transfer), replacing
    the scan driver's default of stacking T ``batch_provider`` calls.

    ``block_batch_provider(t, dev_idx)`` is the streaming round's lazy-batch
    hook (requires ``cfg.k_block``): a traced function returning one
    K-block's [k_block, ...] batch pytree from its [k_block] device indices,
    called inside the round's block scan — the 100k-device path where no
    [K, ...] (or even [k_block-free]) batch stack ever exists on the host.
    ``batch_provider`` may then be ``None``.

    ``recorder``, a :class:`repro.obs.Recorder`, streams the run live: one
    ``chunk`` event per engine dispatch (wall clock, re-trace attribution,
    RSS) fanned out into per-round ``round`` events, plus ``eval`` events.
    All emission happens host-side at chunk boundaries on the
    already-transferred diagnostics — the trajectory (params AND history) is
    bitwise-identical with the recorder on or off.

    This signature is the stable compatibility surface; new scenario axes
    (server optimizer, local steps, participation) are ``FLConfig`` fields,
    and ``repro.fl.Experiment`` is the declarative front door that builds
    cfg/state/providers from one spec and calls here.
    """
    if driver not in DRIVERS:
        raise ValueError(f"unknown driver {driver!r}; one of {DRIVERS}")
    if block_batch_provider is not None and cfg.k_block is None:
        raise ValueError("block_batch_provider streams per-K-block batches "
                         "inside the round scan; set cfg.k_block")
    with obsprof.span("runtime.prologue"):
        opt = server_optimizer(cfg)
        if state.opt_state is None:
            # states built before the server-optimizer axis (or restored from
            # old checkpoints): initialize, with step = rounds already taken so
            # Adam bias correction matches an unbroken run
            state.opt_state = opt.init(state.params)._replace(
                step=jnp.asarray(state.round, jnp.int32))
        opt_state = state.opt_state
        alg = clientlib.get(cfg.client.algo)
        if alg.stateful and state.client_state is None:
            # states built before the client-algorithm axis (or restored from
            # pre-registry checkpoints): zero state, like a fresh setup()
            state.client_state = clientlib.init_state(cfg.client, state.params,
                                                      cfg.num_devices)
        client_state = (
            None if state.client_state is None else
            jax.tree_util.tree_map(jnp.asarray, state.client_state))
        key = jax.random.PRNGKey(cfg.seed + 1)
        h = jnp.asarray(state.h, jnp.float32)
        # perfect CSI is structural: h_hat = None makes the estimate alias h's
        # traced value exactly (leafless carry, hard-zero csi_gain_err)
        perfect_csi = cfg.channel.csi_error == 0.0
        h_hat_np = state.h_hat if state.h_hat is not None else state.h
        h_hat = None if perfect_csi else jnp.asarray(h_hat_np, jnp.float32)
        b = jnp.asarray(state.b, jnp.float32)
        a = jnp.asarray(state.a, jnp.float32)
        eta0 = jnp.asarray(state.eta0, jnp.float32)
        model = chl.get(cfg.channel.model)
        time_varying = cfg.channel.time_varying()
        chan_key = jax.random.PRNGKey(cfg.seed + 2)
        eff_gain = jnp.zeros((), jnp.float32)
        fad_state = None
        if model.has_state:
            if state.fad_state is None:
                raise ValueError(
                    f"channel model {cfg.channel.model!r} threads a "
                    "persistent fading state; FLState.fad_state is unset — "
                    "build the state via setup()")
            fad_state = jnp.asarray(state.fad_state, jnp.float32)
        # geometry-heterogeneous per-device scales ride through the over lane
        # (None — the homogeneous default — keeps the baked-config trace)
        over = None
        if state.scale is not None:
            over = BatchAxes(
                rayleigh_scale=jnp.asarray(state.scale, jnp.float32))
        if time_varying:
            if state.model_dim <= 0:
                raise ValueError("a time-varying channel re-solves "
                                 "Problem 3 with the real model dimension; "
                                 "FLState.model_dim is unset — build the "
                                 "state via setup()")
            # the DESIGNED effective gain: what the server set on its estimate
            eff_gain = jnp.asarray(
                state.a * float(np.sum(np.asarray(h_hat_np, np.float64)
                                       * np.asarray(state.b, np.float64))),
                jnp.float32)
        t0 = state.round
        if driver == "python":
            round_step = make_round_step(cfg, grad_fn, block_batch_provider)
            fading_refresh = _make_fading_refresh(cfg, state.model_dim)
            params = state.params
        else:
            run_chunk = _make_run_chunk(cfg, grad_fn, state.model_dim,
                                        block_batch_provider)
            # params, optimizer state, and client state are donated
            # chunk-to-chunk; copy once so the CALLER's pytrees (often
            # reused across runs, e.g. the benchmark experiments) survive
            params = jax.tree_util.tree_map(jnp.copy, state.params)
            opt_state = jax.tree_util.tree_map(jnp.copy, opt_state)
            client_state = (None if client_state is None else
                            jax.tree_util.tree_map(jnp.copy, client_state))

    hist: Dict[str, List] = {"round": [], "eval_round": []}
    for k in DIAG_KEYS:
        hist[k] = []

    eval_keys: Optional[Tuple[str, ...]] = None

    def record_eval(params, t):
        nonlocal eval_keys
        with obsprof.span("runtime.eval"):
            metrics = eval_fn(params)
            eval_keys = _locked_eval_keys(metrics, eval_keys, t)
            for mk in eval_keys:
                hist.setdefault(mk, []).append(metrics[mk])
            hist["eval_round"].append(t)
            if recorder is not None:
                recorder.on_eval(t, {mk: float(metrics[mk])
                                     for mk in eval_keys})

    if driver == "python":
        for chunk_i, t in enumerate(range(t0 + 1, t0 + num_rounds + 1)):
            with obsprof.annotate_chunk(chunk_i):
                if recorder is not None:
                    tr0 = dict(TRACE_COUNTS)
                    wt0 = time.perf_counter()
                if time_varying:
                    with obsprof.span("runtime.dispatch"):
                        h, h_hat_t, b, a, fad_state = fading_refresh(
                            eff_gain, chan_key, jnp.asarray(t), fad_state,
                            over)
                    h_hat = None if perfect_csi else h_hat_t
                with obsprof.span("runtime.batches"):
                    batch = (None if block_batch_provider is not None
                             else batch_provider(t))
                with obsprof.span("runtime.dispatch"):
                    params, opt_state, client_state, diag = round_step(
                        params, opt_state, client_state, batch, h, h_hat, b,
                        a, eta0, jnp.asarray(t), key)
                hist["round"].append(t)
                with obsprof.span("runtime.history_get"):
                    for k in DIAG_KEYS:
                        hist[k].append(float(diag[k]))
                if recorder is not None:
                    # the python driver's 'chunk' is one round: one (or,
                    # under a time-varying channel, two) dispatches
                    recorder.on_chunk(
                        chunk_i, [t], {k: np.asarray([hist[k][-1]])
                                       for k in DIAG_KEYS},
                        wall_time_s=time.perf_counter() - wt0,
                        dispatches=2 if time_varying else 1,
                        retraces=trace_deltas(tr0),
                        rss_mb=obsprof.rss_mb())
                if eval_fn is not None and (t % eval_every == 0 or t == 1):
                    record_eval(params, t)
    else:
        for chunk_i, ts in enumerate(_plan_chunks(
                t0, num_rounds,
                eval_every if eval_fn is not None else None, chunk_size)):
            with obsprof.annotate_chunk(chunk_i):
                if recorder is not None:
                    tr0 = dict(TRACE_COUNTS)
                    wt0 = time.perf_counter()
                with obsprof.span("runtime.batches"):
                    if block_batch_provider is not None:
                        batches = None  # drawn per (round, K-block) in-scan
                    else:
                        batches = (chunk_batch_provider(ts)
                                   if chunk_batch_provider
                                   else _stack_batches(batch_provider, ts))
                with obsprof.span("runtime.dispatch"):
                    (params, opt_state, client_state, h, h_hat, b, a,
                     fad_state, chunk_hist) = run_chunk(
                         params, opt_state, client_state, h, h_hat, b, a,
                         eta0, key, chan_key, eff_gain, fad_state, over,
                         jnp.asarray(ts, jnp.int32), batches)
                with obsprof.span("runtime.history_get"):
                    # ONE sync per chunk
                    chunk_hist = jax.device_get(chunk_hist)
                hist["round"].extend(ts)
                for k in DIAG_KEYS:
                    hist[k].extend(
                        np.asarray(chunk_hist[k]).astype(float).tolist())
                if recorder is not None:
                    recorder.on_chunk(
                        chunk_i, list(ts),
                        {k: np.asarray(chunk_hist[k]) for k in DIAG_KEYS},
                        wall_time_s=time.perf_counter() - wt0,
                        dispatches=1,
                        retraces=trace_deltas(tr0),
                        rss_mb=obsprof.rss_mb())
                t_end = ts[-1]
                if eval_fn is not None and (t_end % eval_every == 0
                                            or t_end == 1):
                    record_eval(params, t_end)

    with obsprof.span("runtime.epilogue"):
        state.params = params
        state.opt_state = opt_state
        if client_state is not None:
            state.client_state = client_state
        if time_varying:
            # persist the final channel/gain so a second run(cfg, state, ...)
            # resumes from round t0+num_rounds, not the stale round-0 draw
            state.h = np.asarray(jax.device_get(h), np.float64)
            state.h_hat = (state.h if h_hat is None
                           else np.asarray(jax.device_get(h_hat), np.float64))
            state.b = np.asarray(jax.device_get(b), np.float64)
            state.a = float(a)
        if fad_state is not None:
            # the correlated fading process continues where it left off
            state.fad_state = np.asarray(jax.device_get(fad_state), np.float64)
        state.round += num_rounds
    return state, hist


def _stack_trees(trees: Sequence[PyTree]) -> PyTree:
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *trees)


def _slice_tree(tree: PyTree, e: int) -> PyTree:
    return jax.tree_util.tree_map(lambda l: l[e], tree)


def run_batched(cfgs: Sequence[FLConfig], states: Sequence[FLState],
                grad_fn: GradFn, batch_provider: Callable[[int], Any],
                num_rounds: int,
                eval_fn: Optional[Callable[[PyTree], Dict[str, float]]] = None,
                eval_every: int = 10, *, chunk_size: int = 16,
                chunk_batch_provider: Optional[
                    Callable[[Sequence[int]], Any]] = None,
                shard: bool = True,
                recorder: Optional[Any] = None,
                ) -> Tuple[List[FLState], Dict[str, Any]]:
    """Run E experiments as ONE compiled program: the vectorized twin of
    ``run(driver='scan')``.

    The configs must be *structurally identical* (equal
    ``structural_config``): same scheme / case / backend / scenario axes /
    fading mode, differing only in the batchable numerics
    (``BATCHED_FL_FIELDS`` / ``BATCHED_CHANNEL_FIELDS``) — those travel as
    per-experiment traced inputs through ``BatchAxes`` and the stacked
    h/b/a/eta0 channel state, so E grid points cost one trace and one
    dispatch per chunk.  All experiments share ``grad_fn`` and the batch
    providers (one task), the round counter, and the eval schedule.

    When multiple local devices are available and E divides their count, the
    experiment axis is sharded across them
    (``distribution.sharding.experiment_mesh``) — grid points run on
    different devices with no further code change.

    Returns ``(states, hist)`` where each per-round diagnostic in ``hist``
    is an ``np.ndarray`` of shape [E, num_rounds] (same ``DIAG_KEYS`` as
    ``run`` plus the leading experiment axis), eval metrics are
    [E, num_evals], and ``hist['round']`` / ``hist['eval_round']`` stay flat
    lists shared by every experiment.  ``states`` is updated in place per
    experiment exactly like ``run`` updates its single state.

    The mesh backend is not batchable (its device axis IS the mesh); callers
    (``repro.fl.sweep``) fall back to sequential runs there.

    ``recorder`` streams the batched run exactly like ``run``'s: per-chunk
    ``chunk`` events, per-round ``round`` events whose diagnostic values are
    [E] lists, and ``eval`` events with [E] metric lists — host-side only,
    bitwise-invisible to the trajectory.
    """
    if len(cfgs) != len(states) or not cfgs:
        raise ValueError("need equal, nonzero numbers of configs and states")
    num_exp = len(cfgs)
    cfg0 = cfgs[0]
    if cfg0.backend == "mesh":
        raise ValueError("the mesh backend reserves the device axis for the "
                         "FL devices; run mesh experiments sequentially")
    if cfg0.device_mesh is not None:
        raise ValueError(
            "device_mesh (the sharded streaming engine) owns the local "
            "devices for the FL-device axis — a batched run cannot also "
            "shard its experiment axis over them; run device_mesh "
            "experiments sequentially (repro.fl.sweep falls back "
            "automatically)")
    sig = structural_config(cfg0)
    for c in cfgs[1:]:
        if structural_config(c) != sig:
            raise ValueError(
                "configs in a batched run must be structurally identical "
                "(they may differ only in "
                f"{BATCHED_FL_FIELDS + BATCHED_CHANNEL_FIELDS}); got "
                f"{structural_config(c)} vs {sig}")
    t0s = {s.round for s in states}
    if len(t0s) != 1:
        raise ValueError(f"states disagree on the round counter: {t0s}")
    t0 = t0s.pop()
    dims = {s.model_dim for s in states}
    if len(dims) != 1:
        raise ValueError(f"states disagree on model_dim: {dims} — a batched "
                         "run shares one task")
    model_dim = dims.pop()

    with obsprof.span("runtime.prologue"):
        opt = server_optimizer(cfg0)
        alg0 = clientlib.get(cfg0.client.algo)
        for s in states:
            if s.opt_state is None:
                s.opt_state = opt.init(s.params)._replace(
                    step=jnp.asarray(s.round, jnp.int32))
            if alg0.stateful and s.client_state is None:
                s.client_state = clientlib.init_state(cfg0.client, s.params,
                                                      cfg0.num_devices)

        # assemble the per-experiment numerics in NumPy — ONE host->device
        # transfer per stacked array, not one dispatch per experiment (the
        # stacking cost is per run_sweep call, so it must stay off the grid's
        # critical path)
        params = _stack_trees([s.params for s in states])
        opt_state = _stack_trees([s.opt_state for s in states])
        client_state = (_stack_trees([s.client_state for s in states])
                        if alg0.stateful else None)
        h = jnp.asarray(np.stack([np.asarray(s.h) for s in states]),
                        jnp.float32)
        # perfect CSI across the whole sub-batch is structural (h_hat aliases h
        # in-trace); ANY imperfect lane threads the stacked estimates, and the
        # perfect lanes among them stay exact (their estimation noise term is a
        # traced-zero multiple)
        csi_off = all(c.channel.csi_error == 0.0 for c in cfgs)
        h_hat = None if csi_off else jnp.asarray(
            np.stack([np.asarray(s.h_hat if s.h_hat is not None else s.h)
                      for s in states]), jnp.float32)
        b = jnp.asarray(np.stack([np.asarray(s.b) for s in states]),
                        jnp.float32)
        a = jnp.asarray(np.asarray([s.a for s in states]), jnp.float32)
        eta0 = jnp.asarray(np.asarray([s.eta0 for s in states]), jnp.float32)
        keys = jnp.stack([jax.random.PRNGKey(c.seed + 1) for c in cfgs])
        chan_keys = jnp.stack([jax.random.PRNGKey(c.seed + 2) for c in cfgs])
        model = chl.get(cfg0.channel.model)
        time_varying = cfg0.channel.time_varying()
        eff_gain = jnp.zeros((num_exp,), jnp.float32)
        fad_state = None
        if model.has_state:
            if any(s.fad_state is None for s in states):
                raise ValueError(
                    f"channel model {cfg0.channel.model!r} threads a "
                    "persistent fading state; build the states via setup()")
            fad_state = jnp.asarray(
                np.stack([np.asarray(s.fad_state) for s in states]),
                jnp.float32)
        if time_varying:
            if model_dim <= 0:
                raise ValueError("a time-varying channel re-solves "
                                 "Problem 3 with the real model dimension; "
                                 "FLState.model_dim is unset — build the "
                                 "states via setup()")
            eff_gain = jnp.asarray(
                np.asarray([s.a * float(np.sum(np.asarray(
                    s.h_hat if s.h_hat is not None else s.h, np.float64)
                    * np.asarray(s.b, np.float64)))
                    for s in states]), jnp.float32)

        def _scales():
            # in-scan redraw scale: [E, K] geometry-heterogeneous per-device
            # vectors (drawn at setup, living on the states) or [E] scalars
            if cfg0.channel.geometry is not None:
                return jnp.asarray(np.stack([np.asarray(s.scale)
                                             for s in states]), jnp.float32)
            return jnp.asarray(
                np.asarray([c.channel.amplitude_scale() for c in cfgs]),
                jnp.float32)

        over = BatchAxes(
            noise_var=jnp.asarray(
                np.asarray([c.channel.noise_var for c in cfgs]), jnp.float32),
            grad_bound=(None if cfg0.grad_bound is None else jnp.asarray(
                np.asarray([c.grad_bound for c in cfgs]), jnp.float32)),
            b_max=(jnp.asarray(np.asarray([c.channel.b_max for c in cfgs]),
                               jnp.float32) if time_varying else None),
            rayleigh_scale=(_scales() if time_varying else None),
            rho=(jnp.asarray(np.asarray([c.channel.rho for c in cfgs]),
                             jnp.float32) if time_varying else None),
            csi_error=(jnp.asarray(
                np.asarray([c.channel.csi_error for c in cfgs]), jnp.float32)
                if time_varying and not csi_off else None),
            # exactly the numerics the algorithm declares it reads become lanes
            # (an unused lane would change the default traces for nothing)
            client_mu=(jnp.asarray(
                np.asarray([c.client.mu for c in cfgs]), jnp.float32)
                if alg0.uses_mu else None),
            client_alpha=(jnp.asarray(
                np.asarray([c.client.alpha for c in cfgs]), jnp.float32)
                if alg0.uses_alpha else None),
        )

        if shard:
            from repro.distribution import sharding as shardlib
            mesh = shardlib.experiment_mesh(num_exp)
            if mesh is not None:
                (params, opt_state, client_state, h, h_hat, b, a, eta0, keys,
                 chan_keys, eff_gain, fad_state, over) = \
                    shardlib.shard_experiment_axis(
                        (params, opt_state, client_state, h, h_hat, b, a, eta0,
                         keys, chan_keys, eff_gain, fad_state, over), mesh)
        run_chunk = _make_run_chunk_batched(sig, grad_fn, model_dim)

    hist: Dict[str, Any] = {"round": [], "eval_round": []}
    diag_chunks: Dict[str, List[np.ndarray]] = {k: [] for k in DIAG_KEYS}
    eval_chunks: Dict[str, List[List[float]]] = {}
    eval_keys: Optional[Tuple[str, ...]] = None

    def record_eval(params, t):
        nonlocal eval_keys
        with obsprof.span("runtime.eval"):
            per_exp: Dict[str, List[float]] = {}
            for e in range(num_exp):
                metrics = eval_fn(_slice_tree(params, e))
                eval_keys = _locked_eval_keys(metrics, eval_keys, t,
                                              where=f" (experiment {e})")
                for mk in eval_keys:
                    per_exp.setdefault(mk, []).append(metrics[mk])
            for mk in eval_keys:
                eval_chunks.setdefault(mk, []).append(per_exp[mk])
            hist["eval_round"].append(t)
            if recorder is not None:
                recorder.on_eval(t, {mk: [float(v) for v in per_exp[mk]]
                                     for mk in eval_keys})

    for chunk_i, ts in enumerate(_plan_chunks(
            t0, num_rounds, eval_every if eval_fn is not None else None,
            chunk_size)):
        with obsprof.annotate_chunk(chunk_i):
            if recorder is not None:
                tr0 = dict(TRACE_COUNTS)
                wt0 = time.perf_counter()
            with obsprof.span("runtime.batches"):
                batches = (chunk_batch_provider(ts) if chunk_batch_provider
                           else _stack_batches(batch_provider, ts))
            with obsprof.span("runtime.dispatch"):
                (params, opt_state, client_state, h, h_hat, b, a, fad_state,
                 chunk_hist) = run_chunk(
                     params, opt_state, client_state, h, h_hat, b, a, eta0,
                     keys, chan_keys, eff_gain, fad_state, over,
                     jnp.asarray(ts, jnp.int32), batches)
            with obsprof.span("runtime.history_get"):
                chunk_hist = jax.device_get(chunk_hist)  # ONE sync per chunk
            hist["round"].extend(ts)
            for k in DIAG_KEYS:
                diag_chunks[k].append(np.asarray(chunk_hist[k], np.float64))
            if recorder is not None:
                # [E, T] per-chunk diagnostics: on_chunk fans them out into
                # one round event per t with [E] value lists
                recorder.on_chunk(
                    chunk_i, list(ts),
                    {k: np.asarray(chunk_hist[k]) for k in DIAG_KEYS},
                    wall_time_s=time.perf_counter() - wt0,
                    dispatches=1,
                    retraces=trace_deltas(tr0),
                    rss_mb=obsprof.rss_mb())
            t_end = ts[-1]
            if eval_fn is not None and (t_end % eval_every == 0
                                        or t_end == 1):
                record_eval(params, t_end)

    with obsprof.span("runtime.epilogue"):
        for k in DIAG_KEYS:
            hist[k] = np.concatenate(diag_chunks[k], axis=1)       # [E, T]
        for mk, cols in eval_chunks.items():
            hist[mk] = np.asarray(cols, np.float64).T              # [E, evals]

        for e, s in enumerate(states):
            s.params = _slice_tree(params, e)
            s.opt_state = _slice_tree(opt_state, e)
            if alg0.stateful:
                s.client_state = _slice_tree(client_state, e)
            if time_varying:
                s.h = np.asarray(jax.device_get(h[e]), np.float64)
                s.h_hat = (s.h if h_hat is None else
                           np.asarray(jax.device_get(h_hat[e]), np.float64))
                s.b = np.asarray(jax.device_get(b[e]), np.float64)
                s.a = float(a[e])
            if fad_state is not None:
                s.fad_state = np.asarray(jax.device_get(fad_state[e]),
                                         np.float64)
            s.round += num_rounds
    return list(states), hist
