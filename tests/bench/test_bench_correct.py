"""``correct`` at a size a test run can hold: a sound run of the timed
path passes the comparison with the plain reference; the control (the
reference at three bfloat16 passes per product, put in the program's
place) and each
planted fault of the timed path fail it.  The chip check is skipped; the
rest of a run is driven as ``bench/run.py`` drives it."""
import json
import os
import sys
import time

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import correctness, harness  # noqa: E402

SEED = 2147483659
# limits of this small cell on the CPU, on four seeds of each
# configuration: the sound update gap reads at most 1.6e-5 and the
# control's at least 2.3e-4; the error gaps of both read 0, and the
# swapped answers' 0.02 to 0.095 (a step is 0.005: 200 test images a key)
LIMITS = {"init_gap": 1e-5, "update1_gap": 1e-4, "err_gap": 0.01}


def tiny_cell(config="cifar12"):
    """The cell's configuration and traffic at three choice blocks of the
    program's small channel plan (one reduction block), 8x8 images,
    four clients."""
    with open(os.path.join(ROOT, "bench", "configs", config + ".json")) as f:
        cfg = json.load(f)
    with open(os.path.join(ROOT, "bench", "traffic", "xdevice.json")) as f:
        tr = json.load(f)
    cfg["model"].update(choice_blocks=3, channels=[16, 16, 32],
                        stem_channels=16, image=8, params=36780)
    cfg["search"].update(population=2)
    tr.update(clients=4, samples_per_client=150, train_per_client=100,
              test_per_client=50, participation=1.0)
    return harness.Cell("tiny", 1, cfg, tr, dict(LIMITS), [])


@pytest.fixture(scope="module", autouse=True)
def shared_compile_cache(tmp_path_factory):
    """One persistent compilation cache for this module's runs, so a
    program compiled by one run is read back by the next."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache as cc
    names = ("jax_compilation_cache_dir",
             "jax_persistent_cache_min_compile_time_secs",
             "jax_persistent_cache_min_entry_size_bytes")
    old = {n: getattr(jax.config, n) for n in names}
    cc.reset_cache()
    jax.config.update(names[0], str(tmp_path_factory.mktemp("jax_cache")))
    jax.config.update(names[1], 0)
    jax.config.update(names[2], -1)
    yield
    for n, v in old.items():
        jax.config.update(n, v)
    cc.reset_cache()


def _run(cell):
    return harness.run_cell(cell, SEED, 0.5, False, time.perf_counter(),
                            log=lambda *a: None)


@pytest.mark.parametrize("config", ["cifar12", "cifar12-int8"])
def test_sound_run_is_correct(config):
    out = _run(tiny_cell(config))
    assert out["correct"], out["checks"]
    assert out["attempted"] >= 1 and out["failed"] == 0
    assert list(out)[-1] == "checks"


def _control_numbers(config):
    cell = tiny_cell(config)
    fam = harness.family(cell)
    clients = fam.build_fleet(SEED, cell.traffic, cell.config["search"],
                              cell.config["model"])
    shards = {c.cid: (c.train[0], c.train[1], c.test[0], c.test[1])
              for c in clients}
    rng = np.random.default_rng(SEED)

    def keys(n):
        return [rng.integers(0, 4, 3) for _ in range(n)]

    parents, off1, off2 = keys(2), keys(2), keys(2)
    groups = [[0, 1], [2, 3]]
    steps = [{"kind": "fill", "gen": 1, "keys": parents, "groups": groups},
             {"kind": "fill", "gen": 1, "keys": off1, "groups": groups},
             {"kind": "eval", "keys": parents + off1, "clients": [0, 1, 2, 3]},
             {"kind": "fill", "gen": 2, "keys": off2, "groups": groups},
             {"kind": "eval", "keys": parents + off2, "clients": [0, 1, 2, 3]}]
    ref, ctl = fam.Reference(cell.config), fam.control(cell.config)
    ctl_m = ctl.run(SEED, steps, shards)
    return correctness.compare(
        ctl_m, ctl.errors(steps, shards, ctl_m),
        ref.run(SEED, steps, shards), ref.errors(steps, shards, ctl_m))


def test_control_is_not_correct():
    """The reference one precision step below the configuration's (three
    bfloat16 passes per product), put in the program's place."""
    numbers = _control_numbers("cifar12")
    assert any(numbers[k] > LIMITS[k] for k in LIMITS), numbers


def test_int8_control_is_not_correct():
    """The same control for the int8 configuration: its codec rounds
    both sides' payloads, and the control still fails."""
    numbers = _control_numbers("cifar12-int8")
    assert any(numbers[k] > LIMITS[k] for k in LIMITS), numbers


def _unchanged(monkeypatch):
    from repro.engine.backends import VmapBackend
    monkeypatch.setattr(VmapBackend, "_train_fill_fused",
                        lambda self, master, *a, **k: master)


def _half_batch(monkeypatch):
    from repro.engine import backends
    real = backends.client_update_fn

    def half(api, epochs=1, momentum=0.5):
        upd = real(api, epochs, momentum)

        def update(params, key, xb, yb, lr):
            h = xb.shape[1] // 2
            return upd(params, key, xb[:, :h], yb[:, :h], lr)
        return update

    monkeypatch.setattr(backends, "client_update_fn", half)


def _answer(monkeypatch):
    from repro.engine.backends import StackedClientBase
    real = StackedClientBase._rates

    def rates(self, counts, total, n_keys):
        r = real(self, counts, total, n_keys).copy()
        r[0], r[-1] = r[-1], r[0]
        return r

    monkeypatch.setattr(StackedClientBase, "_rates", rates)


FAULTS = pytest.mark.parametrize(
    "fault", [_unchanged, _half_batch, _answer],
    ids=["state_unchanged", "half_batch", "answer_altered"])


@FAULTS
def test_broken_timed_path_is_not_correct(monkeypatch, fault):
    fault(monkeypatch)
    out = _run(tiny_cell())
    assert not out["correct"], out["checks"]


@FAULTS
def test_broken_int8_path_is_not_correct(monkeypatch, fault):
    """The same faults under the int8 route: the codec's round trips,
    error feedback and the Pallas fill_aggregate kernel (interpreted on
    the CPU) sit between the fault and the comparison."""
    fault(monkeypatch)
    out = _run(tiny_cell("cifar12-int8"))
    assert not out["correct"], out["checks"]
