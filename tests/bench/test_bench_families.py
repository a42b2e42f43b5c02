"""Supernet families found by name.

Parity of the move: the CNN family reproduces, exactly, what the
harness read of the small cell of ``test_bench_correct.py`` before the
CNN code moved into ``bench/families/`` (the literals below were taken
from the tree before the move).  A family that is new files only: a
root with its own ``BENCHMARK.json``, configuration and
``families/toy.py`` is found, and a run and a calibration go through
every hook of the family module."""
import hashlib
import json
import os
import sys
import time

import jax
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import harness  # noqa: E402
from bench.families import cnn  # noqa: E402
from test_bench_correct import (  # noqa: E402,F401
    LIMITS, SEED, shared_compile_cache, tiny_cell)

FLEET_SHA256 = ("53efbdde5ea0e8e190762b48f77e40a5"
                "ff4ee778414d839c936d9ed6daaf3f42")
WINDOW_POPULATION = [[3, 2, 2], [1, 1, 0]]
WINDOW_FLOPS = 1859187200.0
SUBNET_MACS = [([0, 0, 0], 36160), ([1, 1, 1], 838976),
               ([2, 2, 2], 471360), ([3, 3, 3], 161856),
               ([3, 2, 2], 354624), ([3, 2, 3], 278592)]
# norm of the reference's change over the first update, per leaf it moved
FIRST_UPDATE_NORMS = {
    "cifar12": {
        "['blocks'][0]['residual']['c1']": 0.03797143375334893,
        "['blocks'][0]['residual']['c2']": 0.03420838541977253,
        "['blocks'][1]['inverted']['dw']": 0.0070163112951368446,
        "['blocks'][1]['inverted']['pw1']": 0.010154328524609273,
        "['blocks'][1]['inverted']['pw2']": 0.016202747801831754,
        "['blocks'][1]['sepconv']['dw1']": 0.018334037648124937,
        "['blocks'][1]['sepconv']['dw2']": 0.014481515879552166,
        "['blocks'][1]['sepconv']['pw1']": 0.020568417759538382,
        "['blocks'][1]['sepconv']['pw2']": 0.018476384917677956,
        "['blocks'][2]['inverted']['dw']": 0.014710353999171576,
        "['blocks'][2]['inverted']['pw1']": 0.023475308093637216,
        "['blocks'][2]['inverted']['pw2']": 0.03512038825774175,
        "['blocks'][2]['sepconv']['dw1']": 0.009102307851580666,
        "['blocks'][2]['sepconv']['dw2']": 0.007922507518773629,
        "['blocks'][2]['sepconv']['pw1']": 0.011868355063625026,
        "['blocks'][2]['sepconv']['pw2']": 0.013524828172390216,
        "['fc']['b']": 0.018764157428134576,
        "['fc']['w']": 0.041895674670979384,
        "['stem']": 0.04313894917354807,
    },
    "cifar12-int8": {
        "['blocks'][0]['inverted']['dw']": 0.01835187533001591,
        "['blocks'][0]['inverted']['pw1']": 0.018207701167112306,
        "['blocks'][0]['inverted']['pw2']": 0.00894520664711012,
        "['blocks'][0]['residual']['c1']": 0.038866426344497135,
        "['blocks'][0]['residual']['c2']": 0.03471944144037073,
        "['blocks'][0]['sepconv']['dw1']": 0.00938999437785484,
        "['blocks'][0]['sepconv']['dw2']": 0.008549461838957583,
        "['blocks'][0]['sepconv']['pw1']": 0.00917874453303411,
        "['blocks'][0]['sepconv']['pw2']": 0.009085230949442137,
        "['blocks'][1]['inverted']['dw']": 0.019672136322168514,
        "['blocks'][1]['inverted']['pw1']": 0.021008109554949393,
        "['blocks'][1]['inverted']['pw2']": 0.0186579815023401,
        "['blocks'][1]['residual']['c1']": 0.009069025454781828,
        "['blocks'][1]['residual']['c2']": 0.009095666328586638,
        "['blocks'][1]['sepconv']['dw1']": 0.020029269764744582,
        "['blocks'][1]['sepconv']['dw2']": 0.01714557871174416,
        "['blocks'][1]['sepconv']['pw1']": 0.02259734789440647,
        "['blocks'][1]['sepconv']['pw2']": 0.02035255797334256,
        "['blocks'][2]['identity']['pw1']": 0.009091747688269994,
        "['blocks'][2]['identity']['pw2']": 0.008945448951571427,
        "['blocks'][2]['inverted']['dw']": 0.023486769719085095,
        "['blocks'][2]['inverted']['pw1']": 0.03002163046763131,
        "['blocks'][2]['inverted']['pw2']": 0.03753671715926287,
        "['blocks'][2]['residual']['c1']": 0.012757167771718099,
        "['blocks'][2]['residual']['c2']": 0.012793378851024504,
        "['blocks'][2]['sepconv']['dw1']": 0.012225943922828988,
        "['blocks'][2]['sepconv']['dw2']": 0.015412928149546916,
        "['blocks'][2]['sepconv']['pw1']": 0.016842376634394536,
        "['blocks'][2]['sepconv']['pw2']": 0.018694597620762712,
        "['fc']['b']": 0.01876249003837298,
        "['fc']['w']": 0.04205269952713787,
        "['stem']": 0.0437743481472346,
    },
}
KEYS = [np.array([1, 2, 3]), np.array([0, 3, 2]), np.array([2, 2, 1]),
        np.array([3, 0, 0])]


def _fleet(cell):
    return harness.family(cell).build_fleet(
        SEED, cell.traffic, cell.config["search"], cell.config["model"])


def test_fleet_matches_parent():
    h = hashlib.sha256()
    for c in _fleet(tiny_cell()):
        for a in (c.train[0], c.train[1], c.test[0], c.test[1]):
            a = np.ascontiguousarray(a)
            h.update(str(a.dtype).encode())
            h.update(str(a.shape).encode())
            h.update(a.tobytes())
    assert h.hexdigest() == FLEET_SHA256


def test_window_population_matches_parent():
    pop = harness.window_population(tiny_cell())
    assert [k.tolist() for k in pop] == WINDOW_POPULATION


def test_window_flops_matches_parent():
    cell = tiny_cell()
    calls = [{"kind": "fill", "keys": KEYS[:2],
              "groups": [np.array([0, 1]), np.array([2, 3])]},
             {"kind": "eval", "keys": KEYS, "clients": np.arange(4)},
             {"kind": "fill", "keys": KEYS[2:],
              "groups": [np.array([3]), np.array([1, 2])]}]
    assert harness.window_flops(cell, calls, _fleet(cell)) == WINDOW_FLOPS


@pytest.mark.parametrize("key,macs", SUBNET_MACS,
                         ids=[str(k) for k, _ in SUBNET_MACS])
def test_subnet_macs_match_parent(key, macs):
    model = tiny_cell().config["model"]
    assert cnn.subnet_macs(key, model) == macs
    assert harness.family(tiny_cell()).sample_macs(key, model) == macs


@pytest.mark.parametrize("config", sorted(FIRST_UPDATE_NORMS))
def test_reference_first_update_matches_parent(config):
    cell = tiny_cell(config)
    shards = {c.cid: (c.train[0], c.train[1], c.test[0], c.test[1])
              for c in _fleet(cell)}
    step = [{"kind": "fill", "gen": 1, "keys": KEYS[:2],
             "groups": [[0, 1], [2, 3]]}]
    before, after = harness.family(cell).Reference(cell.config).run(
        SEED, step, shards)
    flat_a = jax.tree_util.tree_flatten_with_path(before)[0]
    flat_b = jax.tree_util.tree_flatten_with_path(after)[0]
    norms = {jax.tree_util.keystr(p): float(np.linalg.norm(
        np.asarray(b, np.float64) - np.asarray(a, np.float64)))
        for (p, a), (_, b) in zip(flat_a, flat_b)}
    assert {k: v for k, v in norms.items() if v > 0} \
        == FIRST_UPDATE_NORMS[config]


TOY = '''"""A family that delegates to the CNN family and records each call."""
from bench.families import cnn

CALLS = []


def build_fleet(seed, traffic, search, model):
    CALLS.append("build_fleet")
    return cnn.build_fleet(seed, traffic, search, model)


def build_api(model):
    CALLS.append("build_api")
    return cnn.build_api(model)


def sample_macs(key, model):
    CALLS.append("sample_macs")
    return cnn.sample_macs(key, model)


class Reference(cnn.Reference):
    def __init__(self, cfg, three_pass=False, half_batch=False):
        CALLS.append("Reference")
        super().__init__(cfg, three_pass=three_pass, half_batch=half_batch)


def control(cfg):
    CALLS.append("control")
    return cnn.control(cfg)
'''


def _toy_root(root, family="toy", module=TOY):
    """A root holding one cell, ``toy.tiny``, made of new files only: the
    small cell's configuration under the family ``family``, its
    traffic, its limits, and ``families/toy.py``."""
    tiny = tiny_cell()
    cfg = dict(tiny.config, name="toy")
    cfg["model"] = dict(cfg["model"])
    if family is None:
        del cfg["model"]["family"]
    else:
        cfg["model"]["family"] = family
    files = {
        "BENCHMARK.json": {"workloads": [{"name": "toy.tiny", "config": "toy",
                                          "traffic": "tiny", "chips": 1}],
                           "per_layer": []},
        "bench/configs/toy.json": cfg,
        "bench/traffic/tiny.json": dict(tiny.traffic, name="tiny"),
        "bench/limits/toy.tiny.json": LIMITS}
    for rel, obj in files.items():
        path = root / rel
        path.parent.mkdir(parents=True, exist_ok=True)
        path.write_text(json.dumps(obj))
    (root / "bench" / "families").mkdir(exist_ok=True)
    (root / "bench" / "families" / "toy.py").write_text(module)
    return str(root)


def test_new_family_found_by_name(tmp_path):
    root = _toy_root(tmp_path)
    cell = harness.load_cell("toy.tiny", root=root)
    fam = harness.family(cell)
    assert fam.__file__ == os.path.join(root, "bench", "families", "toy.py")
    assert fam.CALLS == []


def test_new_family_drives_every_hook(tmp_path, monkeypatch):
    """A run on the CPU calls the family for the fleet, the supernet, the
    MAC count and the reference; a calibration also for the control."""
    cell = harness.load_cell("toy.tiny", root=_toy_root(tmp_path))
    fam = harness.family(cell)
    out = harness.run_cell(cell, SEED, 0.5, False, time.perf_counter(),
                           log=lambda *a: None)
    assert out["correct"], out["checks"]
    assert set(fam.CALLS) == {"build_fleet", "build_api", "sample_macs",
                              "Reference"}
    fam.CALLS.clear()
    # calibrate.py sets sys.path on import; keep the test session's
    monkeypatch.setattr(sys, "path", list(sys.path))
    from bench import calibrate
    res = calibrate.readings(cell, SEED, control=True,
                             log=lambda *a, **k: None)
    assert set(fam.CALLS) == {"build_fleet", "build_api", "Reference",
                              "control"}
    assert all(res["sound"][k] <= LIMITS[k] for k in LIMITS), res["sound"]


def test_missing_family_key_names_the_file(tmp_path):
    root = _toy_root(tmp_path, family=None)
    cfg = os.path.join(root, "bench", "configs", "toy.json")
    with pytest.raises(ValueError, match="states no family") as e:
        harness.load_cell("toy.tiny", root=root)
    assert cfg in str(e.value)


def test_missing_family_module_names_the_path(tmp_path):
    root = _toy_root(tmp_path, family="absent")
    path = os.path.join(root, "bench", "families", "absent.py")
    with pytest.raises(FileNotFoundError) as e:
        harness.load_cell("toy.tiny", root=root)
    assert path in str(e.value)
