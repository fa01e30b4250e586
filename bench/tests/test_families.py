"""Model families as files (``bench/families/<family>.py``).

The dense family file gives, bit for bit, what the harness gave before
its code moved there: ``data/dense_golden.npz`` holds the weights'
digests for one seed, the ``ModelConfig`` at the rehearsal and the
published sizes, and the reference and float8 control logits of one
sequence at the rehearsal size, all made by the harness as it stood
before the move.  A toy family written to a temporary checkout shows
that a family arrives as new files only: the harness finds it by the
configuration's ``"family"`` and takes its sizes, fan-in and reference
from it.
"""

import dataclasses
import hashlib
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from harness import check, spec, weights
from harness.spec import BENCH

GOLDEN = Path(__file__).parent / "data" / "dense_golden.npz"

TOY_FAMILY = '''
"""A toy family whose one weight has an expert axis: (layers, experts,
d, ff)."""
import numpy as np

REHEARSAL_SIZES = {"hidden_size": 8}
CALLS = []


def model_config(sizes, name):
    return {"name": name, "d": sizes["hidden_size"]}


def fan_in(name, shape):
    return shape[1] if name == "experts" else shape[0]


def logits(params, sizes, tokens, want, quant=None):
    CALLS.append((list(tokens), list(want), quant))
    out = np.zeros((len(want), 4), np.float32)
    out[:, 0] = 1.0
    return out


def shapes(sizes, kv_bytes=2):
    return None
'''


@pytest.fixture(scope="module")
def golden():
    with np.load(GOLDEN) as g:
        return {k: g[k] for k in g.files}


@pytest.fixture(scope="module")
def dense_run(golden):
    from repro.models import build_model

    conf = json.loads((BENCH / "configs" / "smollm-135m.json").read_text())
    fam = spec.family(conf)
    sizes = spec.sizes(conf, rehearse=True)
    cfg = spec.model_config(conf, rehearse=True)
    params = weights.make_params(build_model(cfg).init, int(golden["seed"]),
                                 fam.fan_in)
    return conf, fam, sizes, cfg, params


def _asjson(cfg):
    return json.dumps(dataclasses.asdict(cfg), sort_keys=True, default=str)


def test_dense_model_config_as_before(golden, dense_run):
    conf, _, _, cfg, _ = dense_run
    assert _asjson(cfg) == str(golden["model_config"])
    assert _asjson(spec.model_config(conf)) == str(golden["model_config_full"])


def test_dense_weights_as_before(golden, dense_run):
    params = dense_run[4]
    leaves = jax.tree_util.tree_flatten_with_path(params)[0]
    assert [jax.tree_util.keystr(p) for p, _ in leaves] == list(
        golden["leaf_names"])
    assert [hashlib.sha256(np.asarray(x).tobytes()).hexdigest()
            for _, x in leaves] == list(golden["leaf_sha256"])


@pytest.mark.parametrize("quant,key", [(None, "ref_logits"),
                                       ("fp8", "control_logits")])
def test_dense_reference_as_before(golden, dense_run, quant, key):
    _, fam, sizes, _, params = dense_run
    got = fam.logits(params, sizes, golden["tokens"], golden["want"],
                     quant=quant)
    np.testing.assert_array_equal(got, golden[key])


def _toy_checkout(root: Path, family: str = "toy") -> None:
    (root / "bench" / "families").mkdir(parents=True)
    for d in ("configs", "traffic", "limits"):
        (root / "bench" / d).mkdir()
    (root / "bench" / "families" / "toy.py").write_text(TOY_FAMILY)
    (root / "bench" / "configs" / "toy.json").write_text(json.dumps(
        {"name": "toy", "family": family, "hidden_size": 64}))
    (root / "bench" / "traffic" / "t.json").write_text("{}")
    (root / "bench" / "limits" / "toy.t.json").write_text("{}")
    (root / "BENCHMARK.json").write_text(json.dumps({
        "run_seconds": 1, "end_to_end": [], "per_layer": [],
        "configs": [{"name": "toy", "file": "bench/configs/toy.json"}],
        "workloads": [{"name": "toy.t", "config": "toy", "traffic": "t",
                       "chips": 1}]}))


def test_new_family_arrives_as_new_files(tmp_path):
    _toy_checkout(tmp_path)
    cell = spec.load_cell("toy.t", tmp_path)
    toy = cell.family
    assert toy is spec.family(cell.config, tmp_path)
    assert spec.sizes(cell.config, True, tmp_path)["hidden_size"] == 8
    assert spec.model_config(cell.config, True, tmp_path) == {
        "name": "toy-rehearsal", "d": 8}

    # fan-in from the family: a stacked (L, E, d, ff) weight gets d, not E
    def init(key):
        return {"blocks": {"experts": jnp.zeros((2, 16, 8, 32))}}

    w = np.asarray(weights.make_params(init, 2**33 + 5, toy.fan_in)[
        "blocks"]["experts"])
    assert w.std() == pytest.approx(8 ** -0.5, rel=0.05)

    # the comparison calls the family's reference
    served = check.Served(prompt=[5, 6, 7], tokens=[0, 2])
    gaps = check.served_gaps(toy, None, cell.config, [served])
    assert toy.CALLS == [([5, 6, 7, 0], [2, 3], None)]
    np.testing.assert_array_equal(gaps, [0.0, 1.0])


def test_unknown_family_names_the_file(tmp_path):
    _toy_checkout(tmp_path, family="no_such_family")
    missing = tmp_path / "bench" / "families" / "no_such_family.py"
    with pytest.raises(FileNotFoundError, match=str(missing)):
        spec.load_cell("toy.t", tmp_path)
