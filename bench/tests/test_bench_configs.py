"""A configuration brings its own architecture as new files: its sizes, its
reference module (``bench/references/<config>.py``: plain forward, control,
weight layout, the program's fields) and a ``BENCHMARK.json`` entry. OLMo's
weights are the same bits as before the reference moved into its module."""

import hashlib
import json
import shutil
import time
from pathlib import Path

import jax
import numpy as np
import pytest

from bench import harness, spec, weights
from bench.tests.tiny import alter_served_tokens, tiny_cell

DATA = Path(__file__).parent / "data"
CPU_PEAKS = {"cpu": {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}}
TINY_LIMIT = 0.05
CONFIG = "tiny-nemo"
CELL = "tiny-nemo.online"

# sha256 over (path, bf16 bits) of every weight of the tiny olmo-1b cell,
# computed on the tree before the weight layout moved into
# bench/references/olmo-1b.py
OLMO_DIGESTS = {
    2**31 + 41: "3762195791a768d3a6726e916d307daecb835fbf430964cf33bef7e286ddfd5d",
    7: "336969d4e7324a6b7959993996b2221e7d1f1130abd3d343184a6f953ce76eea",
}


@pytest.mark.parametrize("seed", sorted(OLMO_DIGESTS))
def test_olmo_weights_are_the_same_bits(seed):
    cell = tiny_cell("olmo-1b.offline")
    layout = spec.reference(cell).layout(cell.config)
    dev = jax.sharding.SingleDeviceSharding(jax.devices()[0])
    params = weights.make(layout, seed, weights.as_tree({p: dev if leaf != {} else {} for p, leaf in layout.items()}))
    h = hashlib.sha256()
    for path, leaf in sorted((jax.tree_util.keystr(k), v) for k, v in jax.tree_util.tree_leaves_with_path(params)):
        h.update(path.encode())
        h.update(np.asarray(leaf).view(np.uint16).tobytes())
    assert h.hexdigest() == OLMO_DIGESTS[seed]


@pytest.fixture(scope="module")
def nemo_root(tmp_path_factory):
    """A checkout-like root with a new configuration on the program's
    mistral-nemo-12b entry (RMSNorm with a scale, untied head) at tiny sizes,
    its reference module, a tiny online mix, the repo's metric readers and a
    BENCHMARK.json that names them: new files only."""
    root = tmp_path_factory.mktemp("root")
    olmo = tiny_cell("olmo-1b.shared-prefix", limit=TINY_LIMIT)
    cfg = dict(olmo.config, name=CONFIG, model="mistral-nemo-12b", rope_theta=1e6)
    cfg["architecture"] = dict(cfg["architecture"], norm="rmsnorm", tie_word_embeddings=False)
    for d in ("configs", "traffic", "references"):
        (root / "bench" / d).mkdir(parents=True)
    (root / "bench" / "configs" / f"{CONFIG}.json").write_text(json.dumps(cfg))
    (root / "bench" / "traffic" / "tiny-online.json").write_text(json.dumps(olmo.mix))
    shutil.copy(DATA / "rmsnorm_untied.py", root / "bench" / "references" / f"{CONFIG}.py")
    shutil.copytree(spec.BENCH / "metrics", root / "bench" / "metrics")
    real = spec.load_json(spec.ROOT / "BENCHMARK.json")
    bench = {
        "configs": [{"name": CONFIG, "source": "https://huggingface.co/mistralai/Mistral-Nemo-Base-2407",
                     "file": f"bench/configs/{CONFIG}.json", "reduced": [], "why": "RMSNorm with a scale, untied head"}],
        "workloads": [{"name": CELL, "config": CONFIG, "traffic": "tiny-online", "chips": 1, "why": "test"}],
        "end_to_end": [dict(m, workloads=[CELL]) for m in real["end_to_end"] if spec.applies(m, "olmo-1b.shared-prefix")],
        "per_layer": [],
    }
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


def nemo_run(root, seed, **kw):
    return harness.run(
        CELL, seed=seed, seconds=3.0, trace=False, t_start=time.perf_counter(), require_tpu=False,
        peaks_table=CPU_PEAKS, root=root, **kw,
    )


def test_new_architecture_from_new_files_is_correct_and_its_control_is_not(nemo_root):
    out = nemo_run(nemo_root, 2**31 + 51, control=True)
    cal = out["_calibration"]
    assert cal["program_correct"], cal["gaps"]
    assert max(cal["gaps"]) <= TINY_LIMIT
    assert not out["correct"] and max(cal["control_gaps"]) > TINY_LIMIT
    assert set(out["metrics"]) == {"ttft_p50_s", "itl_p95_s", "setup_s"}
    assert out["attempted"] > 0 and out["failed"] == 0


def test_new_architecture_catches_an_altered_token(nemo_root, monkeypatch):
    alter_served_tokens(monkeypatch)
    out = nemo_run(nemo_root, 2**31 + 52)
    assert not out["correct"]
    assert out["checks"]["served_logit_gap"]["value"] > TINY_LIMIT


def test_a_configuration_without_its_reference_module_is_refused(nemo_root):
    cell = spec.load_cell(CELL, nemo_root)
    assert spec.reference(cell).CONTROL == "fp8"
    with pytest.raises(SystemExit, match="no reference module"):
        spec.reference(spec.Cell(**{**cell.__dict__, "config_name": "absent"}))


def test_a_mesh_that_does_not_lay_out_the_cell_is_refused():
    cfg = tiny_cell("olmo-1b.offline").config
    assert harness.serving_mesh(cfg, 1) is None
    with pytest.raises(SystemExit, match="does not lay"):
        harness.serving_mesh(cfg, 4)
    with pytest.raises(SystemExit, match="does not lay"):
        harness.serving_mesh(dict(cfg, mesh={"data": 2, "model": 2}), 4)


def test_a_reference_of_another_architecture_is_refused():
    cfg = tiny_cell("olmo-1b.offline").config
    ref = spec.reference(tiny_cell("olmo-1b.offline"))
    other = dict(cfg, architecture=dict(cfg["architecture"], norm="rmsnorm"))
    with pytest.raises(ValueError, match="OLMo"):
        ref.layout(other)
    with pytest.raises(SystemExit, match="not the stated architecture"):
        harness.program_config(dict(cfg, model="mistral-nemo-12b"), ref)
