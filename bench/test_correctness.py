"""CPU tests of the comparison that decides ``correct``, at a size a test
run can hold: a sound run of every cell's timed path passes its limits,
the control (the reference in the program's place, matmuls at ``high``)
does not, and neither does a run whose timed path is broken underneath
(state left unchanged, half of each batch left out, an update altered
where it is produced).  Each run goes through the harness's own
``execute`` with the look for a chip skipped."""
from __future__ import annotations

import contextlib
import time

import jax
import pytest

from bench import cells, compare, run, workload

SMALL = {"k20.fixed": {"num_devices": 4, "k_block": None},
         "k1024.fading": {"num_devices": 16, "k_block": 8},
         "k1024.fixed": {"num_devices": 16, "k_block": 8}}
SEED = 2 ** 31 + 77
_JAX_KEYS = ("jax_compilation_cache_dir",
             "jax_persistent_cache_min_compile_time_secs",
             "jax_default_matmul_precision", "jax_enable_compilation_cache")


def small(name: str) -> cells.Cell:
    cell = cells.resolve(name)
    cell.config = dict(cell.config, spec=dict(
        cell.config["spec"], num_train=2000, num_test=200, **SMALL[name]))
    return cell


@pytest.fixture
def isolated():
    """Leave JAX's configuration and the program's caches as found; the
    persistent compilation cache stays off."""
    saved = {k: getattr(jax.config, k) for k in _JAX_KEYS}
    jax.config.update("jax_enable_compilation_cache", False)
    yield
    run.free_program()
    for k, v in saved.items():
        jax.config.update(k, v)


def execute(cell: cells.Cell) -> dict:
    run.free_program()
    return run.execute(cell, SEED, 0.2, False, require_accelerator=False,
                       start=time.perf_counter())


@contextlib.contextmanager
def planted(fault: str):
    """Break the program's timed path underneath the harness."""
    from repro.core import ota
    from repro.fed import runtime
    from repro.fl import tasks

    mp = pytest.MonkeyPatch()
    if fault == "state_unchanged":
        tail = runtime._round_tail

        def unchanged(cfg, sch, opt, params, opt_state, *a, **kw):
            return (params, opt_state) + tail(cfg, sch, opt, params,
                                              opt_state, *a, **kw)[2:]
        mp.setattr(runtime, "_round_tail", unchanged)
    elif fault == "half_batch":
        loss = tasks.mlp_classifier_loss
        mp.setattr(tasks, "mlp_classifier_loss",
                   lambda p, x, y: loss(p, x[:x.shape[0] // 2],
                                        y[:y.shape[0] // 2]))
    else:
        for fn in ("aggregate", "streaming_finish"):
            def negated(*a, _fn=getattr(ota, fn), **kw):
                y = _fn(*a, **kw)
                return {**y, "b3": -y["b3"]}
            mp.setattr(ota, fn, negated)
    try:
        yield
    finally:
        mp.undo()


@pytest.mark.parametrize("name", list(SMALL))
def test_sound_run_is_correct(name, isolated):
    result = execute(small(name))
    assert result["correct"], result["checks"]
    assert list(result)[-1] == "checks"
    assert result["attempted"] >= 1 and result["failed"] == 0


@pytest.mark.parametrize("name", list(SMALL))
def test_control_is_not_correct(name, isolated):
    cell = small(name)
    run.configure_jax(cell.config)
    ref = cells.reference(cell.config).Reference(
        cell.config, *workload.derive_seeds(SEED))
    want = run.follow(ref, cell)
    got = run.follow(ref, cell, precision="high")
    assert not compare.verdict(compare.numbers(got, want, ref.eval_loss),
                               cell.limits)


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch",
                                   "altered_update"])
@pytest.mark.parametrize("name", list(SMALL))
def test_broken_timed_path_is_not_correct(name, fault, isolated):
    with planted(fault):
        result = execute(small(name))
    assert not result["correct"], result["checks"]
