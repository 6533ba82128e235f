"""The benchmark's cells draw what they drew before the harness learnt data
kinds, client-size distributions and frozen trees: each cell's client data,
cohorts, local schedules and starting weights, at two seeds, hash to the
digests recorded from the harness as it was before that change
(``fixtures/cell_digests.json``)."""
import hashlib
import json
from pathlib import Path

import jax
import numpy as np
import pytest

import reference as refm
import spec
import traffic as tr

DIGESTS = Path(__file__).resolve().parent / "fixtures" / "cell_digests.json"
CELLS = [("resnet18_cifar10", "cifar10_iid_c16_stc"), ("femnist_cnn_leaf", "leaf_writers_c100")]
SEEDS = [3, 2**33 + 17]
CLIENTS = range(8)
ROUNDS = range(4)


def _sha(arrays) -> str:
    h = hashlib.sha256()
    for a in arrays:
        a = np.ascontiguousarray(np.asarray(a))
        h.update(f"{a.dtype.str}{a.shape}".encode())
        h.update(a.tobytes())
    return h.hexdigest()


def digests(bench, config_name: str, traffic_name: str, seed: int):
    """What a cell draws from one seed, as one hash per kind of draw."""
    config, traffic = bench.config(config_name), bench.traffic(traffic_name)
    fed = tr.Federation(traffic, config, seed)
    data = [a for i in CLIENTS for a in fed.data(i)]
    cohorts = [np.asarray(fed.cohort_of(r)) for r in ROUNDS]
    schedules = [tr.client_schedule(fed, i, r) for r in ROUNDS for i in CLIENTS]
    weights = refm.init_weights(bench.reference(config_name), config, seed)
    return {"data": _sha(data), "cohorts": _sha(cohorts), "schedules": _sha(schedules),
            "init_weights": _sha(jax.device_get(jax.tree_util.tree_leaves(weights)))}


def _key(config_name, seed):
    return f"{config_name}/{seed}"


@pytest.mark.parametrize("config_name,traffic_name", CELLS)
@pytest.mark.parametrize("seed", SEEDS)
def test_cells_draw_what_they_drew_before(config_name, traffic_name, seed):
    want = json.loads(DIGESTS.read_text())[_key(config_name, seed)]
    assert digests(spec.Bench(), config_name, traffic_name, seed) == want
