"""An architecture is files: a sparse-expert configuration (a view with its
seeding rules, a plain reference, a configuration, a traffic mix: ``data/
sparse_experts``) is added to a copy of the benchmark WITHOUT editing one
file that is there, and runs to the last line on the CPU — what the next
``model_config`` PR does at a published model's widths. And the seeded
weights of what exists are, byte for byte, the parent commit's."""

import hashlib
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
ADDED = Path(__file__).resolve().parent / "data" / "sparse_experts"

_RUN = """
import json, sys
from benchmark.run import Cell, run_cell
res = run_cell(Cell(sys.argv[1]), seed=int(sys.argv[2]), seconds=2.0,
               trace=False, require_tpu=False)
print(json.dumps(res))
"""


def _files(root: Path):
    return {p.relative_to(root): p for p in sorted(root.rglob("*"))
            if p.is_file() and "__pycache__" not in p.parts}


@pytest.fixture(scope="module")
def grown(tmp_path_factory):
    """A copy of BENCHMARK.json and benchmark/ with the added files laid
    over it and the added entries appended."""
    tmp = tmp_path_factory.mktemp("checkout")
    shutil.copytree(ROOT / "benchmark", tmp / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    added = []
    for rel, src in _files(ADDED).items():
        if rel.name == "entries.json":
            continue
        dst = tmp / "benchmark" / rel
        assert not dst.exists(), f"{rel} would overwrite a file that is there"
        shutil.copy(src, dst)
        added.append(Path("benchmark") / rel)
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    entries = json.loads((ADDED / "entries.json").read_text())
    bench["configs"] += entries["configs"]
    bench["workloads"] += entries["workloads"]
    for kind in ("end_to_end", "per_layer"):
        for m in bench[kind]:
            m.get("workloads", []).extend(entries[kind].get(m["name"], []))
    (tmp / "BENCHMARK.json").write_text(json.dumps(bench, indent=1))
    return tmp, added, entries


def test_a_sparse_expert_configuration_is_added_as_files_and_is_correct(grown):
    tmp, _, entries = grown
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join([str(tmp), str(ROOT)]))
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    proc = subprocess.run(
        [sys.executable, "-c", _RUN, entries["workloads"][0]["name"],
         str(2 ** 31 + 99)], cwd=tmp, env=env, capture_output=True,
        text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-3000:]
    lines = proc.stdout.strip().splitlines()
    res = json.loads(lines[-1])
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] > 0
    assert set(res["metrics"]) == {"setup_s", "out_tok_per_s"}
    ref = next(line for line in lines if line.startswith("reference: "))
    assert "ok=True" in ref


def test_no_file_that_was_there_differs(grown):
    tmp, added, entries = grown
    was = _files(ROOT / "benchmark")
    now = _files(tmp / "benchmark")
    assert set(now) - set(was) == {p.relative_to("benchmark") for p in added}
    for rel, path in was.items():
        assert now[rel].read_bytes() == path.read_bytes(), rel
    old = json.loads((ROOT / "BENCHMARK.json").read_text())
    new = json.loads((tmp / "BENCHMARK.json").read_text())
    assert set(new) == set(old)
    for key in ("command", "paths", "run_seconds"):
        assert new[key] == old[key]
    for key in ("configs", "workloads"):
        assert new[key][:len(old[key])] == old[key]
        assert len(new[key]) == len(old[key]) + len(entries[key])
    for key in ("end_to_end", "per_layer"):
        assert len(new[key]) == len(old[key])
        for a, b in zip(old[key], new[key]):
            assert {k: v for k, v in a.items() if k != "workloads"} == {
                k: v for k, v in b.items() if k != "workloads"}
            mine = a.get("workloads", [])
            assert b.get("workloads", [])[:len(mine)] == mine


def _moe_model():
    from ray_dynamic_batching_tpu.models.causal_lm import TINY_MOE, CausalLM

    return CausalLM(TINY_MOE, name="rehearsal")


def test_a_leaf_nobody_has_a_rule_for_still_raises():
    import jax.numpy as jnp

    from benchmark.weights import make_params

    with pytest.raises(ValueError, match="no rule for parameter moe/w"):
        make_params(_moe_model(), 1, jnp.float32)
    with pytest.raises(ValueError, match="no rule for parameter moe/w"):
        make_params(_moe_model(), 1, jnp.float32, lambda names, shape: None)


def test_a_views_seeding_rules_draw_each_expert_at_its_own_fan_in():
    import importlib.util

    import jax.numpy as jnp
    import numpy as np

    from benchmark.weights import make_params

    spec = importlib.util.spec_from_file_location(
        "sparse_experts_view", ADDED / "views" / "sparse_experts.py")
    view = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(view)
    params = make_params(_moe_model(), 5, jnp.float32, view.seeding)
    moe = params["params"]["layer0"]["moe"]
    for name, fan_in in (("wi", 64), ("wg", 64), ("wo", 128)):
        std = float(np.asarray(moe[name]).std())
        assert abs(std * fan_in ** 0.5 - 1.0) < 0.05, (name, std)
    assert abs(float(np.asarray(moe["router"]["kernel"]).std()) * 8 - 1) < 0.15
    assert np.all(np.asarray(
        params["params"]["layer0"]["mlp_norm"]["scale"]) == 1.0)
    # the two layers' stacks come from one draw, split: they differ
    assert not np.array_equal(
        np.asarray(moe["wi"]),
        np.asarray(params["params"]["layer1"]["moe"]["wi"]))


# sha256 over every leaf's path, dtype, shape and bytes of make_params at
# benchmark/tests/tiny.py's widths, taken from the parent commit (23ddd09)
# on the CPU before views and seeding rules came in.
PARENT = json.loads((Path(__file__).resolve().parent / "data"
                     / "make_params_parent_digests.json").read_text())


def _digest(tree) -> str:
    import jax
    import numpy as np

    h = hashlib.sha256()
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        a = np.asarray(leaf)
        h.update(jax.tree_util.keystr(path).encode())
        h.update(str(a.dtype).encode())
        h.update(str(a.shape).encode())
        h.update(a.tobytes())
    return h.hexdigest()


@pytest.mark.parametrize("key", sorted(PARENT))
def test_seeded_weights_of_what_exists_are_the_parents_bytes(key):
    import jax.numpy as jnp

    from benchmark import views
    from benchmark.run import model_factory
    from benchmark.tests.tiny import tiny_cell
    from benchmark.weights import make_params

    workload, seed, dtype = key.split("/")
    cfg = tiny_cell(workload).config
    model = model_factory(cfg["program"], "x")()
    seeding = getattr(views.get(cfg.get("view", "dense")), "seeding", None)
    params = make_params(model, int(seed), jnp.dtype(dtype), seeding)
    assert _digest(params) == PARENT[key]
