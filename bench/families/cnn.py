"""The CNN supernet family: the paper's choice blocks of identity,
residual, inverted-residual and depthwise-separable branches over
CIFAR-shaped images (arXiv:2003.02793, Fig. 3 and Sec. IV.C).

What the harness asks of a family, and nothing more:

  * ``build_fleet(seed, traffic, search, model)`` — the clients, from the
    seed;
  * ``build_api(model)`` — the program's supernet through its public
    entry points, checked against the configuration's parameter count;
  * ``sample_macs(key, model)`` — forward MACs of one image through the
    sub-model ``key`` selects;
  * ``Reference`` and ``control`` — the plain reference that decides
    ``correct`` (``cnn_reference.py``).

The MAC count restates the supernet of ``models/cnn.py`` from the
configuration file's channel plan (it is pinned against
``repro.core.flops.cnn_subnet_macs`` in the tests).
"""
from __future__ import annotations

from typing import Dict, Sequence

# the family's reference, as the harness asks for it
from bench.families.cnn_reference import (  # noqa: F401
    BRANCHES, Reference, control)


def build_fleet(seed: int, traffic: Dict, search: Dict, model: Dict):
    """The fleet's clients from the seed: one synthetic CIFAR-shaped
    sample set, split IID, each client's shard cut into train and test
    batches."""
    from repro.data import make_classification, make_clients, partition_iid
    if traffic["partition"] != "iid":
        raise ValueError(f"unknown partition {traffic['partition']!r}")
    k = traffic["clients"]
    n = k * traffic["samples_per_client"]
    x, y = make_classification(seed, n, image=model["image"])
    clients = make_clients(x, y, partition_iid(seed, n, k),
                           batch=search["batch"],
                           test_batch=search["test_batch"], seed=seed)
    want_tr = traffic["train_per_client"] // search["batch"]
    want_te = traffic["test_per_client"] // search["test_batch"]
    for c in clients:
        if c.train[0].shape[0] != want_tr or c.test[0].shape[0] != want_te:
            raise ValueError(
                f"client {c.cid}: {c.train[0].shape[0]} train and "
                f"{c.test[0].shape[0]} test batches, the traffic file "
                f"states {want_tr} and {want_te}")
    return clients


def build_api(model: Dict):
    """The program's supernet (``cnn_supernet_api``) at the
    configuration's depth."""
    from repro.configs import get_config
    from repro.core.supernet import cnn_supernet_api
    if model["choice_blocks"] != len(model["channels"]):
        raise ValueError(f"{model['choice_blocks']} choice blocks, but a "
                         f"channel plan of {len(model['channels'])}")
    api = cnn_supernet_api(get_config(model["program"]).replace(
        num_layers=model["choice_blocks"]))
    if api.master_params() != model["params"]:
        raise ValueError(f"the program's supernet has "
                         f"{api.master_params()} parameters, the "
                         f"configuration states {model['params']}")
    return api


def _conv_macs(h, w, cin, cout, k, stride=1, groups=1) -> int:
    return (h // stride) * (w // stride) * cout * (cin // groups) * k * k


def branch_macs(name: str, h: int, w: int, cin: int, cout: int) -> int:
    """MACs of one choice-block branch on an ``h`` x ``w`` input; a block
    whose channels change is a reduction block (stride 2).  Strided
    depthwise work is counted at output resolution."""
    stride = 2 if cout != cin else 1
    ho, wo = h // stride, w // stride
    if name == "identity":
        return 0 if stride == 1 else 2 * _conv_macs(h, w, cin, cout // 2,
                                                    1, 2)
    if name == "residual":
        return (_conv_macs(h, w, cin, cout, 3, stride)
                + _conv_macs(ho, wo, cout, cout, 3))
    if name == "inverted":
        hid = 4 * cin
        return (_conv_macs(h, w, cin, hid, 1)
                + _conv_macs(h, w, hid, hid, 3, stride, groups=hid)
                + _conv_macs(ho, wo, hid, cout, 1))
    if name == "sepconv":
        return (_conv_macs(h, w, cin, cin, 3, stride, groups=cin)
                + _conv_macs(ho, wo, cin, cout, 1)
                + _conv_macs(ho, wo, cout, cout, 3, groups=cout)
                + _conv_macs(ho, wo, cout, cout, 1))
    raise ValueError(f"unknown branch {name!r}")


def subnet_macs(key: Sequence[int], model: Dict) -> int:
    """Forward MACs of one image through the subnet ``key`` selects, for
    the configuration file's ``model`` section (``channels``,
    ``stem_channels``, ``image``, ``classes``)."""
    chans = model["channels"]
    cin = model["stem_channels"]
    h = w = model["image"]
    total = _conv_macs(h, w, 3, cin, 3)
    for i, cout in enumerate(chans):
        total += branch_macs(BRANCHES[int(key[i])], h, w, cin, cout)
        if cout != cin:
            h, w = h // 2, w // 2
        cin = cout
    return int(total + cin * model["classes"])


sample_macs = subnet_macs
