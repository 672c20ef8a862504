"""A configuration names what checks it: `check.reference`, `check.driver`
and the check's sizes are resolved from the configuration's own file to files
found by name, first under the data root and then under benchmarks/, and a
named file that is missing stops the boot.  Shown on throw-away copies of
reference.py and paged_step.py, in-process and through run.py --rehearse."""

import ast
import glob
import json
import os
import shutil
import subprocess
import sys
from types import SimpleNamespace

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)
sys.path.insert(0, ROOT)

import serve  # noqa: E402

SPEC = {"serving": {"page_size": 16}}


def throwaway_root(root: str, tol: float = 0.0123) -> None:
    """references/throwaway.py and drivers/throwaway.py under `root`: the
    benchmark's own reference and driver, marked so a test can tell them."""
    os.makedirs(os.path.join(root, "references"), exist_ok=True)
    os.makedirs(os.path.join(root, "drivers"), exist_ok=True)
    with open(os.path.join(BENCH, "reference.py")) as f:
        ref = f.read()
    with open(os.path.join(root, "references", "throwaway.py"), "w") as f:
        f.write(ref + f'\nTOLERANCE = {{"value": {tol!r}, '
                '"why": "a test value, to be found in the result"}\n')
    with open(os.path.join(BENCH, "paged_step.py")) as f:
        drv = f.read()
    with open(os.path.join(root, "drivers", "throwaway.py"), "w") as f:
        f.write(drv.replace(
            '    import numpy as np\n\n    ids = np.asarray(token_ids',
            '    import numpy as np\n\n'
            '    print("throwaway driver called", flush=True)\n'
            '    ids = np.asarray(token_ids'))


def test_without_the_keys_the_check_is_todays():
    c = serve.resolve_check(SPEC, BENCH)
    assert (c["reference"], c["driver"]) == ("reference", "paged_step")
    assert c["reference_mod"].__file__ == os.path.join(BENCH, "reference.py")
    assert c["driver_mod"].__file__ == os.path.join(BENCH, "paged_step.py")
    # tol None: compare_logits picks the dense or the routed tolerance
    assert c["tol"] is None
    assert (c["n_prefill"], c["n_decode"], c["pages_per_seq"],
            c["page_size"]) == (64, 4, 8, 16)


def test_named_files_are_found_under_the_data_root_first(tmp_path):
    root = str(tmp_path)
    throwaway_root(root)
    spec = dict(SPEC, check={"reference": "throwaway", "driver": "throwaway",
                             "n_prefill": 100, "n_decode": 6,
                             "pages_per_seq": 7})
    c = serve.resolve_check(spec, root)
    assert c["reference"] == "references/throwaway"
    assert c["driver"] == "drivers/throwaway"
    assert c["reference_mod"].__file__.startswith(root)
    assert c["driver_mod"].__file__.startswith(root)
    assert c["tol"] == 0.0123
    assert (c["n_prefill"], c["n_decode"], c["pages_per_seq"]) == (100, 6, 7)


@pytest.mark.parametrize("check,error", [
    ({"reference": "nowhere"}, FileNotFoundError),
    ({"driver": "nowhere"}, FileNotFoundError),
    ({"driver": "../paged_step"}, ValueError),  # a name, not a path
    ({"refrence": "throwaway"}, ValueError),  # a misspelt key is no default
    ({"n_prefill": 200}, ValueError),  # 8 pages of 16 hold 128 tokens
])
def test_a_missing_file_or_a_wrong_key_stops_the_boot(tmp_path, check, error):
    throwaway_root(str(tmp_path))
    with pytest.raises(error):
        serve.resolve_check(dict(SPEC, check=check), str(tmp_path))


def test_the_named_files_do_the_check(tmp_path, capsys):
    """logit_check on a tiny model with both files named: the copies run,
    the comparison is reference.compare_logits with the file's tolerance,
    and the check is taken past the default 64 + 4 tokens."""
    import jax

    from kafka_tpu.models import ModelConfig, init_params

    root = str(tmp_path)
    throwaway_root(root, tol=1e-4)
    cfg = ModelConfig(
        name="t", vocab_size=300, hidden_size=64, intermediate_size=96,
        num_layers=2, num_heads=8, num_kv_heads=2, head_dim=8,
        rope_theta=1e4, rms_norm_eps=1e-5, tie_word_embeddings=False,
        dtype="float32", num_experts=0, num_experts_per_tok=2)
    engine = SimpleNamespace(cfg=cfg,
                             params=init_params(cfg, jax.random.PRNGKey(1)))
    spec = dict(SPEC, check={"reference": "throwaway", "driver": "throwaway",
                             "n_prefill": 80, "n_decode": 3})
    res = serve.logit_check(SimpleNamespace(engine=engine),
                            serve.resolve_check(spec, root))
    assert res["ok"], res
    assert res["tol"] == 1e-4 and res["compared"] == 4
    assert res["reference"] == "references/throwaway"
    assert res["driver"] == "drivers/throwaway"
    assert "throwaway driver called" in capsys.readouterr().out


def reference_files():
    return sorted(glob.glob(os.path.join(BENCH, "references", "*.py"))
                  + [os.path.join(BENCH, "reference.py")])


@pytest.mark.parametrize("path", reference_files(),
                         ids=lambda p: os.path.relpath(p, BENCH))
def test_a_reference_shares_no_code_with_the_program(path):
    with open(path) as f:
        tree = ast.parse(f.read())
    for node in ast.walk(tree):
        names = []
        if isinstance(node, ast.Import):
            names = [a.name for a in node.names]
        elif isinstance(node, ast.ImportFrom):
            names = [node.module or ""]
        for n in names:
            assert n.split(".")[0] != "kafka_tpu", (
                f"{path} imports {n}: a reference is written from the "
                "published description, not from the program")


# --------------------------------------------------------------------------
# through run.py: a throw-away configuration in a copy of the tiny data root
# --------------------------------------------------------------------------

def rehearse(root, cell="throwaway.chat-decode"):
    return subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--root", root,
         "--workload", cell, "--seed", "3", "--seconds", "4", "--trace", "0",
         "--rehearse"],
        cwd=ROOT, env=dict(os.environ, JAX_PLATFORMS="cpu"), timeout=400,
        capture_output=True, text=True)


def throwaway_cell(tmp_path, check):
    """A copy of the tiny root plus, as NEW files and entries only, a
    configuration that names its reference, its driver and its scopes."""
    root = str(tmp_path / "tiny")
    shutil.copytree(os.path.join(HERE, "tiny"), root)
    throwaway_root(root)
    with open(os.path.join(root, "configs", "tiny-moe.json")) as f:
        spec = json.load(f)
    # names the program registers; a new block's would stand here
    spec.update(check=check, scopes=["moe_router", "moe_experts"])
    cfg_file = os.path.join(root, "configs", "throwaway.json")
    with open(cfg_file, "w") as f:
        json.dump(spec, f)
    shutil.copy(os.path.join(root, "workloads", "tiny-moe.chat-decode.json"),
                os.path.join(root, "workloads", "throwaway.chat-decode.json"))
    bench_file = os.path.join(root, "BENCHMARK.json")
    with open(bench_file) as f:
        bench = json.load(f)
    bench["configs"].append({"name": "throwaway", "source": "none",
                             "file": cfg_file, "reduced": [], "why": "test"})
    bench["workloads"].append({
        "name": "throwaway.chat-decode", "config": "throwaway",
        "traffic": "chat-decode", "chips": 1, "why": "test"})
    with open(bench_file, "w") as f:
        json.dump(bench, f)
    return root


def test_a_throwaway_configuration_is_served_and_checked_by_its_files(
        tmp_path):
    root = throwaway_cell(tmp_path, {
        "reference": "throwaway", "driver": "throwaway", "n_prefill": 96,
        "n_decode": 2})
    p = rehearse(root)
    assert p.returncode == 0, p.stdout[-2000:] + p.stderr[-2000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert line["failed"] == 0 and line["attempted"] > 0
    printed = next(ln for ln in p.stdout.splitlines()
                   if ln.startswith("run.py: logit check "))
    check = json.loads(printed[len("run.py: logit check "):])
    assert check["reference"] == "references/throwaway"
    assert check["driver"] == "drivers/throwaway"
    assert check["tol"] == 0.0123 and check["compared"] >= 2
    assert check["ok"], check
    with open(os.path.join(ROOT, ".bench_out", "throwaway.chat-decode",
                           "serve.log")) as f:
        assert "throwaway driver called" in f.read()


@pytest.mark.parametrize("edit,said", [
    ({"check": {"driver": "nowhere"}}, "no drivers/nowhere.py"),
    ({"scopes": ["moe_dispatch"]}, "does not register"),
])
def test_a_boot_with_a_missing_file_or_scope_prints_no_result(
        tmp_path, edit, said):
    root = throwaway_cell(tmp_path, {"reference": "throwaway"})
    cfg_file = os.path.join(root, "configs", "throwaway.json")
    with open(cfg_file) as f:
        spec = json.load(f)
    spec.update(edit)
    with open(cfg_file, "w") as f:
        json.dump(spec, f)
    p = rehearse(root)
    assert p.returncode == 4, p.stdout[-2000:] + p.stderr[-2000:]
    assert said in p.stderr
    assert not any(ln.startswith("{") for ln in p.stdout.splitlines())
