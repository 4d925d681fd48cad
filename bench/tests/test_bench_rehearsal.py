"""Each cell's run rehearsed on the CPU at its configuration's small
widths, the Pallas kernels in interpret mode where the interpreter is
fast enough: the harness's own function from set-up through the window
to the check."""

import io
import json
import os
import shutil
import subprocess
import sys
import time
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(ROOT / "src"))

import harness  # noqa: E402

SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in SPEC["workloads"]]
KEYS = ["correct", "attempted", "failed", "metrics", "device"]


@pytest.fixture
def short_warmup(monkeypatch):
    """Two seconds of warm-up traffic instead of the mix's own."""
    load = harness.load_cell

    def quick(root, name):
        cell = load(root, name)
        cell.mix["warmup"] = dict(cell.mix["warmup"], seconds=2)
        return cell

    monkeypatch.setattr(harness, "load_cell", quick)


@pytest.fixture
def pallas_interpret(monkeypatch):
    """The engine's kernel dispatch takes the Pallas kernels, run by the
    interpreter."""
    from repro.kernels import ops
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    for name in ("_batched_paged_pallas", "_chunked_prefill_pallas"):
        kernel = getattr(ops, name)
        monkeypatch.setattr(
            ops, name,
            lambda *a, _k=kernel, **kw: _k(*a, **{**kw, "interpret": True}))


def rehearse(root, cell, *, trace=False, seconds=3.0):
    return harness.run(root, cell, 3_000_000_017, seconds, trace,
                       t_process=time.perf_counter(), smoke=True,
                       log=io.StringIO())


# the interpreter runs a Pallas grid step by step: the danube cells'
# deeper page tables and longer prompts take it minutes to serve a
# request, so they rehearse on the kernels' reference forms
INTERPRET = {"smollm135m-chat-poisson"}


@pytest.mark.parametrize("cell", CELLS)
def test_cell_rehearses_end_to_end(cell, short_warmup, request):
    if cell in INTERPRET:
        request.getfixturevalue("pallas_interpret")
    out = rehearse(ROOT, cell)
    assert list(out)[:5] == KEYS and list(out)[-1] == "checks"
    assert out["correct"] is True, out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert out["checks"]["requests_compared"]["value"] >= 1
    want = {m["name"] for m in harness.load_cell(ROOT, cell).metrics}
    assert set(out["metrics"]) == want
    assert all(m["value"] > 0 for m in out["metrics"].values())
    assert out["device"]["platform"] == "cpu"
    json.dumps(out)


def test_per_layer_metrics_from_a_cpu_trace(short_warmup):
    cell = "danube1.8b-rag-closed"
    out = rehearse(ROOT, cell, trace=True)
    m = out["metrics"]
    # what needs no chip: host spans, the program's counters, the trace;
    # shares of a chip's peaks are left out without the chip's peaks
    peaks = {"decode_mfu", "prefill_mfu", "paged_decode_roofline",
             "chunked_prefill_roofline", "peak_hbm_share",
             "prefill_mfu.rag", "chunked_prefill_roofline.rag"}
    want = {x["name"] for x in harness.load_cell(ROOT, cell).layer_metrics}
    assert set(m) == want - peaks
    assert 0 < m["prefix_hit_token_share"]["value"] < 100
    assert 0 < m["kv_pages_in_use_share"]["value"] < 100
    assert 0 < out["device"]["busy_s"] <= out["device"]["window_s"]
    for key in ("device_ops", "idle_gaps"):
        assert 0 < len(out["breakdown"][key]) <= 10


def test_a_cell_added_as_files_is_found_by_name(tmp_path, short_warmup):
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    mix = json.loads((BENCH / "traffic" / "chat-poisson.json").read_text())
    mix["arrivals"]["rate_rps"] = 3.0
    (tmp_path / "bench" / "traffic" / "chat-slow.json").write_text(
        json.dumps(mix))
    spec = dict(SPEC, workloads=SPEC["workloads"] + [{
        "name": "smollm135m-chat-slow", "config": "smollm-135m",
        "traffic": "chat-slow", "chips": 1, "why": "a slower chat mix"}])
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    out = rehearse(tmp_path, "smollm135m-chat-slow", seconds=2.0)
    assert out["attempted"] == 6          # 3 requests/s for 2 s
    assert out["correct"] is True


FAMILY = '''"""A dense decoder whose residual projections start scaled by
(2 L) ** -0.5, as GPT-2 initialises them."""
from families import dense

shapes, logits = dense.shapes, dense.logits


def model_config(conf, s):
    """Served with no window in any layer: ModelConfig holds one window
    for all layers."""
    return dense.model_config(conf, dict(s, windows=[None] * s["L"]))


def init_params(conf, seed, device=None):
    p = dense.init_params(conf, seed, device)
    scale = (2 * conf["num_hidden_layers"]) ** -0.5
    attn, mlp = p["layers"]["attn"], p["layers"]["mlp"]
    attn["wo"], mlp["w2"] = attn["wo"] * scale, mlp["w2"] * scale
    return p
'''


def tree(root: Path) -> dict:
    return {p.relative_to(root): p.read_bytes() for p in root.rglob("*")
            if p.is_file() and "__pycache__" not in p.parts}


# the new family serves every layer without a window and its reference
# windows alternate layers: it accepts what is served where no served
# position reaches the window (the mix's max_len), and refuses it where
# the served sequences pass a window of 8, so the check reads the
# family's own reference
@pytest.mark.parametrize("window,correct", [(1024, True), (8, False)])
def test_a_configuration_added_as_files_is_found_by_name(
        tmp_path, short_warmup, window, correct):
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    new = {Path("bench/families/dense_scaled.py"): FAMILY.encode()}
    conf = json.loads((BENCH / "configs" / "smollm-135m.json").read_text())
    L = conf["smoke"]["num_hidden_layers"]
    conf.update(name="smollm-135m-scaled", family="dense_scaled")
    conf["smoke"] = dict(conf["smoke"], sliding_window=window, layer_types=[
        ("full_attention", "sliding_attention")[i % 2] for i in range(L)])
    new[Path("bench/configs/smollm-135m-scaled.json")] = json.dumps(
        conf).encode()
    mix = json.loads((BENCH / "traffic" / "chat-poisson.json").read_text())
    mix["arrivals"]["rate_rps"] = 3.0
    new[Path("bench/traffic/chat-slow.json")] = json.dumps(mix).encode()
    for path, data in new.items():
        (tmp_path / path).write_bytes(data)
    spec = dict(SPEC, configs=SPEC["configs"] + [{
        "name": "smollm-135m-scaled", "source": "a test",
        "file": "bench/configs/smollm-135m-scaled.json", "reduced": [],
        "why": "a dense variant"}], workloads=SPEC["workloads"] + [{
            "name": "scaled-chat-slow", "config": "smollm-135m-scaled",
            "traffic": "chat-slow", "chips": 1, "why": "a new family"}])
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))

    cell = harness.load_cell(tmp_path, "scaled-chat-slow")
    assert cell.family.__file__ == str(tmp_path / "bench" / "families"
                                       / "dense_scaled.py")
    s = cell.family.shapes(dict(cell.conf, **cell.conf["smoke"]))
    assert s["windows"] == [None, window] * (L // 2)
    out = rehearse(tmp_path, "scaled-chat-slow", seconds=2.0)
    assert out["attempted"] == 6          # 3 requests/s for 2 s
    assert out["checks"]["requests_compared"]["value"] >= 1
    assert out["correct"] is correct, out["checks"]
    # the new files, and no other file of the benchmark changed
    copied = tree(tmp_path / "bench")
    repo = {p: b for p, b in tree(BENCH).items() if p.parts[0] != "tests"}
    assert {Path("bench") / p for p in set(copied) - set(repo)} == set(new)
    assert all(copied[p] == b for p, b in repo.items())


def test_engine_keys_by_name():
    e = json.loads((BENCH / "traffic" / "rag-closed.json").read_text())[
        "engine"]
    ec = harness.engine_config(e, 65536)
    assert (ec.n_slots, ec.max_len, ec.prompt_buckets, ec.paged,
            ec.prefix_cache, ec.prefix_cache_pages) == (
        23, 2304, (1024, 1280, 1536, 1792, 2048), True, True, 768)
    # another EngineConfig field passes through by name
    assert harness.engine_config(dict(e, batch_wait_steps=2),
                                 65536).batch_wait_steps == 2
    # a key that is no field, or one the harness works out itself
    for key in ("page_size_tokens", "n_slots", "paged", "prefix_cache"):
        with pytest.raises(ValueError, match=key):
            harness.engine_config(dict(e, **{key: 1}), 65536)


def test_no_chip_no_result(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    cmd = [sys.executable, str(ROOT / "bench" / "run.py"), "--workload",
           CELLS[0], "--seed", "1", "--seconds", "1", "--trace", "0"]
    p = subprocess.run(cmd, cwd=ROOT, env=env, capture_output=True,
                       text=True, timeout=120)
    assert p.returncode != 0 and p.stdout.strip() == ""
    # a directory with only the benchmark's files, and no program
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    cmd[1] = str(tmp_path / "bench" / "run.py")
    p = subprocess.run(cmd, cwd=tmp_path, env=env, capture_output=True,
                       text=True, timeout=120)
    assert p.returncode != 0 and p.stdout.strip() == ""
