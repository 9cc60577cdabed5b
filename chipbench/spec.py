"""Where a cell's files are, and what BENCHMARK.json must look like.

A cell is one entry of ``workloads`` in ``BENCHMARK.json``. Everything that
belongs to it is found BY NAME, so a later PR adds files and one entry and
edits nothing that exists:

  config  ``<root>/configs/<config>.json``     sizes as they are run
  mix     ``<root>/mixes/<traffic>.json``      ``kind`` + that kind's parameters
  driver  ``<root>/drivers/<kind>.py``         ``run(ctx) -> dict``
  reader  ``<root>/layer_metrics/<metric>.py`` ``read(trace, spans, counters, cell)``

``<root>`` is the directory of the configuration's ``file`` entry two levels
up (``chipbench/`` for this PR's cells); mixes, drivers and readers are
looked up in every directory listed under ``paths``, first match wins.
"""

from __future__ import annotations

import importlib.util
import json
import os
import re
from typing import Any, Dict, List, Optional

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCHMARK_FILE = os.path.join(REPO_ROOT, "BENCHMARK.json")

NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH_RE = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")
# a width may never be cut (the contract): these may not appear in `reduced`
WIDTH_KEYS = ("hidden_size", "intermediate_size", "head_dim", "d_model",
              "d_ff", "num_experts_per_tok", "rotary_pct")

TOP_KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"}
CONFIG_KEYS = {"name", "source", "file", "reduced", "why"}
WORKLOAD_KEYS = {"name", "config", "traffic", "chips", "why"}
E2E_KEYS = {"name", "unit", "better", "bound", "source"}
LAYER_KEYS = {"name", "unit", "better", "source", "layer", "moves"}


class SpecError(ValueError):
    """BENCHMARK.json or one of a cell's files is not as the contract says."""


def load_json(path: str) -> Any:
    with open(path) as f:
        return json.load(f)


def load_benchmark(path: str = BENCHMARK_FILE) -> Dict[str, Any]:
    return load_json(path)


def _one_line(s: Any, what: str) -> None:
    if not (isinstance(s, str) and 1 <= len(s) <= 200
            and "\n" not in s and "\t" not in s):
        raise SpecError(f"{what}: 1 to 200 characters on one line, no tab")


def _find(bench: Dict[str, Any], sub: str, stem: str,
          suffixes=(".py",), root: str = REPO_ROOT) -> Optional[str]:
    for p in bench["paths"]:
        for suf in suffixes:
            cand = os.path.join(root, p, sub, stem + suf)
            if os.path.isfile(cand):
                return cand
    return None


def find_mix(bench, traffic: str, root: str = REPO_ROOT) -> Optional[str]:
    return _find(bench, "mixes", traffic, (".json",), root)


def find_driver(bench, kind: str, root: str = REPO_ROOT) -> Optional[str]:
    return _find(bench, "drivers", kind, root=root)


def find_reader(bench, metric: str, root: str = REPO_ROOT) -> Optional[str]:
    return _find(bench, "layer_metrics", metric, root=root)


def load_module(path: str):
    """Import a driver or reader from its file (metric names hold dots, so
    these are not importable by module path)."""
    name = "chipbench_dyn_" + re.sub(r"[^A-Za-z0-9_]", "_",
                                     os.path.relpath(path, REPO_ROOT))
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metrics_of_cell(bench: Dict[str, Any], cell: str, group: str
                    ) -> List[Dict[str, Any]]:
    """The ``end_to_end`` or ``per_layer`` metrics cell ``cell`` reports: a
    metric without a ``workloads`` key is reported in every cell."""
    return [m for m in bench[group]
            if "workloads" not in m or cell in m["workloads"]]


def validate(bench: Dict[str, Any], root: str = REPO_ROOT) -> None:
    """Raise SpecError where ``bench`` breaks the contract's static rules or
    names a file that is not there. What only a chip run can show (spread
    against the bounds, memory floor) is the driver's to check."""
    if set(bench) != TOP_KEYS:
        raise SpecError(f"top-level keys {sorted(bench)} != {sorted(TOP_KEYS)}")
    paths = bench["paths"]
    if not (1 <= len(paths) <= 16):
        raise SpecError("1 to 16 paths")
    for p in paths:
        if not PATH_RE.match(p) or p.startswith("/") or ".." in p.split("/"):
            raise SpecError(f"path {p!r}")
    cmd = bench["command"]
    if not (isinstance(cmd, list) and 1 <= len(cmd) <= 32):
        raise SpecError("command: a list of at most 32 strings")
    for w in cmd:
        _one_line(w, f"command word {w!r}")
        if w.startswith("/") or ".." in w.split("/"):
            raise SpecError(f"command word {w!r} leaves the repo")
    rs = bench["run_seconds"]
    if not (isinstance(rs, int) and 1 <= rs <= 51):
        raise SpecError("run_seconds: a whole number from 1 to 51")

    def under_paths(f: str) -> bool:
        return any(f == p or f.startswith(p.rstrip("/") + "/") for p in paths)

    def names(entries, what):
        seen = set()
        for e in entries:
            n = e.get("name")
            if not (isinstance(n, str) and NAME_RE.match(n)):
                raise SpecError(f"{what} name {n!r}")
            if n in seen:
                raise SpecError(f"{what} name {n!r} twice")
            seen.add(n)
        return seen

    configs = bench["configs"]
    if not (1 <= len(configs) <= 24):
        raise SpecError("1 to 24 configs")
    config_names = names(configs, "config")
    files = set()
    for c in configs:
        if set(c) != CONFIG_KEYS:
            raise SpecError(f"config {c.get('name')}: keys {sorted(c)}")
        _one_line(c["source"], f"config {c['name']} source")
        _one_line(c["why"], f"config {c['name']} why")
        f = c["file"]
        if not PATH_RE.match(f) or not under_paths(f) or f in files:
            raise SpecError(f"config {c['name']}: file {f!r}")
        files.add(f)
        if not os.path.isfile(os.path.join(root, f)):
            raise SpecError(f"config {c['name']}: {f} is not there")
        if len(c["reduced"]) > 16:
            raise SpecError(f"config {c['name']}: over 16 reduced keys")
        for k in c["reduced"]:
            if not NAME_RE.match(k):
                raise SpecError(f"config {c['name']}: reduced key {k!r}")
            if k in WIDTH_KEYS or k.endswith(("_dim", "_rank")):
                raise SpecError(f"config {c['name']}: {k} is a width and "
                                f"may never be reduced")
        held = load_json(os.path.join(root, f))
        if sorted(held.get("reduced", [])) != sorted(c["reduced"]):
            raise SpecError(f"config {c['name']}: `reduced` differs between "
                            f"BENCHMARK.json and {f}")

    cells = bench["workloads"]
    if not (1 <= len(cells) <= 24):
        raise SpecError("1 to 24 workloads")
    cell_names = names(cells, "workload")
    pairs = set()
    for w in cells:
        if set(w) != WORKLOAD_KEYS:
            raise SpecError(f"workload {w.get('name')}: keys {sorted(w)}")
        if w["config"] not in config_names:
            raise SpecError(f"workload {w['name']}: config {w['config']!r}")
        if not NAME_RE.match(w["traffic"]):
            raise SpecError(f"workload {w['name']}: traffic {w['traffic']!r}")
        if w["chips"] not in (1, 4):
            raise SpecError(f"workload {w['name']}: chips must be 1 or 4")
        _one_line(w["why"], f"workload {w['name']} why")
        pair = (w["config"], w["traffic"])
        if pair in pairs:
            raise SpecError(f"pair {pair} appears twice")
        pairs.add(pair)
        mix_path = find_mix(bench, w["traffic"], root)
        if mix_path is None:
            raise SpecError(f"workload {w['name']}: no mix file "
                            f"mixes/{w['traffic']}.json")
        kind = load_json(mix_path).get("kind")
        if not kind or find_driver(bench, kind, root) is None:
            raise SpecError(f"workload {w['name']}: mix kind {kind!r} has "
                            f"no drivers/{kind}.py")
    used = {w["config"] for w in cells}
    if used != config_names:
        raise SpecError(f"configs used by no cell: {config_names - used}")
    four = sum(1 for w in cells if w["chips"] == 4)
    if four > max(1, len(cells) // 4):
        raise SpecError(f"{four} four-chip cells of {len(cells)}: at most "
                        f"25% (and one always)")

    e2e = bench["end_to_end"]
    layer = bench["per_layer"]
    if not (1 <= len(e2e) <= 16) or not (1 <= len(layer) <= 128):
        raise SpecError("1 to 16 end_to_end and 1 to 128 per_layer metrics")
    names(e2e + layer, "metric")

    def common(m, keys):
        extra = set(m) - keys - {"workloads"}
        if extra or (keys - set(m)):
            raise SpecError(f"metric {m['name']}: keys {sorted(m)}")
        if not UNIT_RE.match(m["unit"]):
            raise SpecError(f"metric {m['name']}: unit {m['unit']!r}")
        if m["better"] not in ("lower", "higher"):
            raise SpecError(f"metric {m['name']}: better {m['better']!r}")
        if m["source"] not in SOURCES:
            raise SpecError(f"metric {m['name']}: source {m['source']!r}")
        for c in m.get("workloads", []):
            if c not in cell_names:
                raise SpecError(f"metric {m['name']}: no cell {c!r}")

    for m in e2e:
        common(m, E2E_KEYS)
        if m["source"] not in ("host_clock", "device_trace"):
            raise SpecError(f"end-to-end metric {m['name']}: source must be "
                            f"host_clock or device_trace")
        b = m["bound"]
        if not (isinstance(b, (int, float)) and 0.01 <= b <= 0.1):
            raise SpecError(f"metric {m['name']}: bound {b!r} outside "
                            f"[0.01, 0.1]")
    if "setup_s" not in {m["name"] for m in e2e}:
        raise SpecError("one end-to-end metric must be setup_s")
    for m in layer:
        common(m, LAYER_KEYS)
        _one_line(m["layer"], f"metric {m['name']} layer")
        if find_reader(bench, m["name"], root) is None:
            raise SpecError(f"metric {m['name']}: no reader "
                            f"layer_metrics/{m['name']}.py")
    for w in cells:
        mine = {m["name"] for m in metrics_of_cell(bench, w["name"],
                                                   "end_to_end")}
        if "setup_s" not in mine or len(mine) < 2:
            raise SpecError(f"cell {w['name']}: needs setup_s and one other "
                            f"end-to-end metric")
        lay = metrics_of_cell(bench, w["name"], "per_layer")
        if not lay:
            raise SpecError(f"cell {w['name']}: no per-layer metric")
        for m in lay:
            if m["moves"] not in mine:
                raise SpecError(f"metric {m['name']} moves {m['moves']!r}, "
                                f"which cell {w['name']} does not report")
    if len(json.dumps(bench)) > 64 * 1024:
        raise SpecError("BENCHMARK.json over 64 KiB")


def _deep_update(base: Dict[str, Any], over: Dict[str, Any]) -> Dict[str, Any]:
    out = dict(base)
    for k, v in over.items():
        if isinstance(v, dict) and isinstance(out.get(k), dict):
            out[k] = _deep_update(out[k], v)
        else:
            out[k] = v
    return out


def load_cell(bench: Dict[str, Any], name: str, *, rehearsal: bool = False,
              root: str = REPO_ROOT) -> Dict[str, Any]:
    """Everything one run needs to know about cell ``name``. In rehearsal
    the ``rehearsal`` group of the config and of the mix overrides the real
    sizes (toy size, CPU)."""
    for w in bench["workloads"]:
        if w["name"] == name:
            break
    else:
        raise SpecError(f"no workload {name!r} in BENCHMARK.json; have "
                        f"{[w['name'] for w in bench['workloads']]}")
    entry = next(c for c in bench["configs"] if c["name"] == w["config"])
    config = load_json(os.path.join(root, entry["file"]))
    mix = load_json(find_mix(bench, w["traffic"], root))
    if rehearsal:
        config = _deep_update(config, config.get("rehearsal", {}))
        mix = _deep_update(mix, mix.get("rehearsal", {}))
    return {"name": name, "chips": w["chips"], "why": w["why"],
            "config_name": w["config"], "config": config,
            "traffic": w["traffic"], "mix": mix,
            "end_to_end": metrics_of_cell(bench, name, "end_to_end"),
            "per_layer": metrics_of_cell(bench, name, "per_layer")}


def gpt_config_kwargs(config: Dict[str, Any]) -> Dict[str, Any]:
    """GPTConfig keyword arguments from a configuration file: the published
    (Hugging Face GPT-NeoX) keys at the top level mapped onto the program's
    names, then the file's own ``model`` group (dtypes, path flags)."""
    import jax.numpy as jnp
    if config.get("rotary_emb_base", 10000) != 10000:
        raise SpecError("models/gpt.py fixes the rotary base at 10000")
    kw = {"d_model": config["hidden_size"],
          "num_heads": config["num_attention_heads"],
          "num_layers": config["num_hidden_layers"],
          "d_ff": config["intermediate_size"],
          "vocab_size": config["vocab_size"],
          "max_seq_len": config["max_position_embeddings"],
          "rotary": True,
          "rotary_pct": config["rotary_pct"],
          "parallel_residual": config["use_parallel_residual"],
          "tie_embeddings": config["tie_word_embeddings"],
          "layer_norm_eps": config["layer_norm_eps"]}
    kw.update(config.get("model", {}))
    for k in ("dtype", "param_dtype"):
        if isinstance(kw.get(k), str):
            kw[k] = jnp.dtype(kw[k]).type
    return kw
