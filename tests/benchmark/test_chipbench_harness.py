"""The harness end to end in its CPU rehearsal, the contract's static rules,
and the data-driven requirement: a configuration, a mix, a cell and a
per-layer metric are added as files plus one BENCHMARK.json entry each."""

import copy
import json
import os
import shutil
import subprocess
import sys
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from chipbench import spec  # noqa: E402
from chipbench.harness import SUFFIX_REHEARSAL  # noqa: E402

BENCH = spec.load_benchmark()
# cells that ran on the chip but cannot carry a bound yet wait, as the
# entries a later PR adds, under chipbench/later/; the rehearsal runs them too
LATER = [spec.load_json(os.path.join(ROOT, "chipbench", "later", f))
         for f in sorted(os.listdir(os.path.join(ROOT, "chipbench", "later")))]
FULL = copy.deepcopy(BENCH)
for entries in LATER:
    for group in ("workloads", "end_to_end", "per_layer"):
        FULL[group].extend(entries[group])
CELLS = [w["name"] for w in FULL["workloads"]]
RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}
SEED = 3_000_000_011        # more than 32 signed bits hold, as the driver's


def _launch(root, cell, chips, trace, script="run.py", args=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=f"--xla_force_host_platform_device_count={chips}",
               PYTHONPATH=ROOT)
    env.pop("BENCH_RUN", None)
    args = args or ["--workload", cell, "--seed", str(SEED), "--seconds", "2",
                    "--trace", str(trace), "--rehearsal"]
    return subprocess.Popen(
        [sys.executable, os.path.join(root, "chipbench", script)] + args,
        cwd=root, env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
        text=True)


def _added_copy(tmp):
    """A copy of the benchmark alone, with one configuration, one mix, one
    cell and one per-layer metric ADDED: new files and new entries, no file
    that exists edited."""
    root = os.path.join(tmp, "copy")
    shutil.copytree(os.path.join(ROOT, "chipbench"),
                    os.path.join(root, "chipbench"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = {}
    for d, _, fs in os.walk(root):
        for f in fs:
            p = os.path.join(d, f)
            before[p] = open(p, "rb").read()
    cfg = spec.load_json(os.path.join(ROOT, "chipbench", "configs",
                                      "pythia-1.4b-cut.json"))
    cfg["rehearsal"]["num_hidden_layers"] = 3
    with open(os.path.join(root, "chipbench", "configs", "added-cfg.json"),
              "w") as f:
        json.dump(cfg, f)
    mix = spec.load_json(os.path.join(ROOT, "chipbench", "mixes",
                                      "pretrain-2k.json"))
    mix["rehearsal"]["seq_len"] = 32
    with open(os.path.join(root, "chipbench", "mixes", "added-mix.json"),
              "w") as f:
        json.dump(mix, f)
    with open(os.path.join(root, "chipbench", "layer_metrics",
                           "steps_in_window.added.py"), "w") as f:
        f.write("def read(trace, spans, counters, cell):\n"
                "    return counters['steps']\n")
    bench = copy.deepcopy(FULL)
    bench["configs"].append({
        "name": "added-cfg", "source": cfg["source"],
        "file": "chipbench/configs/added-cfg.json",
        "reduced": cfg["reduced"], "why": "added by a test"})
    bench["workloads"].append({
        "name": "added-cell", "config": "added-cfg", "traffic": "added-mix",
        "chips": 1, "why": "added by a test"})
    for m in bench["end_to_end"]:
        if m["name"] == "train_tokens_per_s":
            m["workloads"].append("added-cell")
    bench["per_layer"].append({
        "name": "steps_in_window.added", "unit": "steps", "better": "higher",
        "source": "program_counter", "layer": "trainer",
        "moves": "train_tokens_per_s", "workloads": ["added-cell"]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    for p, data in before.items():
        assert open(p, "rb").read() == data, f"{p} was edited"
    return root, bench


@pytest.fixture(scope="module")
def runs(tmp_path_factory):
    """Every cell once in rehearsal (trace 0), one serving cell traced, and
    the added cell (traced) — all started together, from a copy of the
    benchmark alone whose BENCHMARK.json holds the later and added cells."""
    tmp = str(tmp_path_factory.mktemp("chipbench"))
    added_root, added_bench = _added_copy(tmp)
    procs = {}
    for w in FULL["workloads"]:
        procs[(w["name"], 0)] = _launch(added_root, w["name"], w["chips"], 0)
    procs[("serve-chat", 1)] = _launch(added_root, "serve-chat", 1, 1)
    procs[("serve-batch", 1)] = _launch(added_root, "serve-batch", 1, 1)
    procs[("added-cell", 1)] = _launch(added_root, "added-cell", 1, 1)
    procs["sweep"] = _launch(
        added_root, None, 1, 0, script="sweep_open.py",
        args=["--config", "pythia-6.9b-l16", "--mix", "chat-steady",
              "--rates", "4,8", "--seed", str(SEED), "--seconds", "1",
              "--rehearsal"])
    out = {}
    for key, p in procs.items():
        so, se = p.communicate(timeout=600)
        out[key] = types.SimpleNamespace(rc=p.returncode, out=so, err=se)
    out["added"] = (added_root, added_bench)
    return out


@pytest.mark.parametrize("cell", CELLS)
def test_cell_runs_end_to_end_in_rehearsal(runs, cell):
    r = runs[(cell, 0)]
    assert r.rc == 0, r.err[-3000:] + r.out[-3000:]
    last = json.loads(r.out.strip().splitlines()[-1])
    assert set(last) == RESULT_KEYS
    assert last["correct"] is True, r.out[-3000:]
    assert last["attempted"] > 0 and last["failed"] == 0
    want = {m["name"] + SUFFIX_REHEARSAL
            for m in spec.metrics_of_cell(FULL, cell, "end_to_end")}
    assert set(last["metrics"]) == want         # renamed: not device numbers
    for m in last["metrics"].values():
        assert set(m) == {"value", "unit"} and m["value"] > 0
    dev = last["device"]
    chips = next(w["chips"] for w in FULL["workloads"] if w["name"] == cell)
    assert dev["platform"] == "cpu" and dev["count"] == chips
    assert "memory_peak_bytes" in dev
    assert "REHEARSAL" in r.out


def test_four_chip_cell_divides_its_state_over_four_virtual_devices(runs):
    out = runs[("train-zero3-4chip", 0)].out
    assert "as ZeRO-3 over dp=4 says" in out
    assert "[0.25, 0.25, 0.25, 0.25]" in out


@pytest.mark.parametrize("cell", ["train-1chip", "train-zero3-4chip"])
def test_training_cells_hold_the_trainer_to_the_reference(runs, cell):
    """Forward by single tokens, backward by the gradient's norm, the
    optimizer by the loss it lowered; and the reference is taken a layer at
    a time, never from a whole copy on one device."""
    out = runs[(cell, 0)].out
    for line in ("ok: 8 single tokens' NLL through eval_batch",
                 "ok: loss of the seeded weights",
                 "ok: a step on one row repeated: gradient norm",
                 "ok: the row's loss after that optimizer step"):
        assert line in out, out[-3000:]
    assert "FAILED" not in out
    src = open(os.path.join(ROOT, "chipbench", "drivers", "train.py")).read()
    assert "device_put(params" not in src


def test_closed_loop_traced_run_reads_live_kv_from_the_traced_stretch(runs):
    r = runs[("serve-batch", 1)]
    assert r.rc == 0, r.err[-3000:] + r.out[-3000:]
    last = json.loads(r.out.strip().splitlines()[-1])
    names = {n[:-len(SUFFIX_REHEARSAL)] for n in last["metrics"]}
    assert {"occupancy.batch", "kv_live_share.batch",
            "ttft_ms.p50.batch"} <= names
    assert "warm-up traffic: 0.5s" in r.out     # a fixed time, from any seed


def test_sweep_prints_attainment_per_rate_and_no_result_line(runs):
    r = runs["sweep"]
    assert r.rc == 0, r.err[-3000:] + r.out[-3000:]
    lines = [x for x in r.out.splitlines() if "SWEEP rate" in x]
    assert len(lines) == 2 and "4.0/s" in lines[0] and "8.0/s" in lines[1]
    assert all("% met" in x and "programs built inside it: []" in x
               for x in lines)
    assert not r.out.strip().endswith("}")


def test_traced_run_reports_per_layer_metrics_under_the_cells_names(runs):
    r = runs[("serve-chat", 1)]
    assert r.rc == 0, r.err[-3000:] + r.out[-3000:]
    last = json.loads(r.out.strip().splitlines()[-1])
    assert RESULT_KEYS <= set(last) <= RESULT_KEYS | {"breakdown"}
    names = {n[:-len(SUFFIX_REHEARSAL)] for n in last["metrics"]}
    mine = {m["name"] for m in spec.metrics_of_cell(FULL, "serve-chat",
                                                    "per_layer")}
    # the CPU has no device plane: readers of the device trace find nothing
    # to read and are left out of the line; the client's and the program's
    # counts are there
    assert names <= mine
    assert {"queue_wait_ms.p95.chat", "host_ms_per_chunk.chat",
            "ttft_ms.p50.chat", "tpot_ms.p50.chat", "slo_share.chat"} <= names
    assert not {m for m in names if "device_idle" in m or "decode_step" in m}


def test_added_config_mix_cell_and_metric_need_no_edit(runs):
    root, bench = runs["added"]
    spec.validate(bench, root=root)
    r = runs[("added-cell", 1)]
    assert r.rc == 0, r.err[-3000:] + r.out[-3000:]
    last = json.loads(r.out.strip().splitlines()[-1])
    assert last["correct"] is True
    assert last["metrics"]["steps_in_window.added" + SUFFIX_REHEARSAL][
        "value"] == last["attempted"]
    # the copy held the benchmark alone and borrowed the program from ROOT
    assert sorted(f for f in os.listdir(root) if not f.startswith(".")) == \
        ["BENCHMARK.json", "chipbench"]


def test_benchmark_alone_cannot_run(runs, tmp_path):
    """In a directory that holds only BENCHMARK.json and the files under
    `paths` the run exits non-zero and prints no result."""
    root, _ = runs["added"]
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    p = subprocess.run(
        [sys.executable, os.path.join(root, "chipbench", "run.py"),
         "--workload", "added-cell", "--seed", "1", "--seconds", "1",
         "--trace", "0", "--rehearsal"], cwd=root, env=env,
        capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert not p.stdout.strip().endswith("}")


# ------------------------------------------------------- device gatekeeping
def _main_with_devices(monkeypatch, devices, cell):
    import jax
    from chipbench import run
    monkeypatch.setattr(jax, "devices", lambda *a, **k: devices)
    return run.main(["--workload", cell, "--seed", "1", "--seconds", "1",
                     "--trace", "0"])


def _dev(platform, kind):
    return types.SimpleNamespace(platform=platform, device_kind=kind, id=0)


def test_no_chip_exits_non_zero_and_prints_no_result(monkeypatch, capsys):
    rc = _main_with_devices(monkeypatch, [_dev("cpu", "cpu")], "train-1chip")
    out = capsys.readouterr()
    assert rc != 0 and "needs a TPU" in out.err and "{" not in out.out


def test_unknown_device_kind_exits_non_zero(monkeypatch, capsys):
    rc = _main_with_devices(monkeypatch, [_dev("tpu", "TPU v9 imaginary")],
                            "train-1chip")
    out = capsys.readouterr()
    assert rc != 0 and "peaks.json" in out.err and "{" not in out.out


def test_fewer_chips_than_the_cell_asks_exits_non_zero(monkeypatch, capsys):
    rc = _main_with_devices(monkeypatch, [_dev("tpu", "TPU v5 lite")],
                            "train-zero3-4chip")
    out = capsys.readouterr()
    assert rc != 0 and "asks for 4" in out.err and "{" not in out.out


def test_rehearsal_refuses_a_chip(monkeypatch, capsys):
    import jax
    from chipbench import run
    monkeypatch.setattr(jax, "devices",
                        lambda *a, **k: [_dev("tpu", "TPU v5 lite")])
    rc = run.main(["--workload", "train-1chip", "--rehearsal"])
    assert rc != 0 and "{" not in capsys.readouterr().out


# --------------------------------------------------------- BENCHMARK.json
def test_benchmark_json_validates():
    spec.validate(BENCH)
    spec.validate(FULL)         # and with the cells that wait under later/
    assert set(BENCH) == spec.TOP_KEYS
    four = [w for w in BENCH["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(BENCH["workloads"]) // 4)
    for path in BENCH["paths"]:
        assert os.path.isdir(os.path.join(ROOT, path))
    assert "setup_s" in {m["name"] for m in BENCH["end_to_end"]}
    # a full check of 24 cells at this length fits the driver's 43,200 s
    assert (2 + 14 * 24) * (BENCH["run_seconds"] + 60) + 24 * 180 + 1200 \
        <= 43200


def test_every_layer_metric_moves_a_metric_its_cells_report():
    for w in BENCH["workloads"]:
        e2e = {m["name"] for m in spec.metrics_of_cell(BENCH, w["name"],
                                                       "end_to_end")}
        layer = spec.metrics_of_cell(BENCH, w["name"], "per_layer")
        assert layer and "setup_s" in e2e and len(e2e) >= 2
        for m in layer:
            assert m["moves"] in e2e, (w["name"], m["name"])
            assert spec.find_reader(BENCH, m["name"]) is not None
        assert spec.find_mix(BENCH, w["traffic"]) is not None


def _broken(edit):
    bench = copy.deepcopy(BENCH)
    edit(bench)
    return bench


@pytest.mark.parametrize("edit,why", [
    (lambda b: b["workloads"][0].update(name="has space"), "name"),
    (lambda b: b["end_to_end"][0].update(unit="tokens per second"), "unit"),
    (lambda b: b["end_to_end"][0].update(bound=0.2), "bound"),
    (lambda b: b["end_to_end"][0].update(source="program_counter"), "source"),
    (lambda b: b["per_layer"][0].update(moves="train_tokens_per_s"), "moves"),
    (lambda b: b["per_layer"][0].update(name="no_reader_for_this"), "reader"),
    (lambda b: b["workloads"][0].update(traffic="no-such-mix"), "mix"),
    (lambda b: b["workloads"][0].update(chips=2), "chips"),
    (lambda b: [w.update(chips=4) for w in b["workloads"][:2]], "25%"),
    (lambda b: b["configs"][0].update(reduced=["hidden_size"]), "width"),
    (lambda b: b["configs"][0].update(file="deepspeed_tpu/x.json"), "file"),
    (lambda b: b.update(run_seconds=60), "run_seconds"),
    (lambda b: b.update(extra=1), "keys"),
    (lambda b: b["per_layer"][0].update(why="no such key"), "keys"),
    (lambda b: b["command"].append("../outside"), "command"),
    (lambda b: b["end_to_end"].pop(), "setup_s"),
    (lambda b: b["workloads"].append(dict(b["workloads"][0], name="again")),
     "pair"),
])
def test_validate_refuses(edit, why):
    with pytest.raises(spec.SpecError):
        spec.validate(_broken(edit))


def test_configuration_files_hold_what_the_contract_asks():
    for c in BENCH["configs"]:
        held = spec.load_json(os.path.join(ROOT, c["file"]))
        assert held["source"] == c["source"]
        assert held["reduced"] == c["reduced"]
        assert set(held["reduced"]) == set(held["reduced_notes"])
        for key in ("assumed", "deployment", "model", "engine", "chips",
                    "rehearsal", "architecture"):
            assert key in held, (c["name"], key)
        kw = spec.gpt_config_kwargs(held)
        assert kw["d_model"] == held["hidden_size"]
        assert kw["d_model"] // kw["num_heads"] == 128     # heads of 128
        # a rehearsal never changes a key that is not a size
        assert set(held["rehearsal"]) <= {
            "hidden_size", "num_attention_heads", "num_hidden_layers",
            "intermediate_size", "vocab_size", "max_position_embeddings",
            "engine"}
    cut = spec.load_json(os.path.join(ROOT, "chipbench/configs/pythia-1.4b-cut.json"))
    full = spec.load_json(os.path.join(ROOT, "chipbench/configs/pythia-1.4b.json"))
    differ = {k for k in full if k not in ("reduced", "reduced_notes", "engine",
                                           "chips", "deployment")
              and full[k] != cut[k]}
    assert differ == {"num_hidden_layers"}     # cut in depth only
