"""Finds everything a cell is made of by the names in BENCHMARK.json, and
lints that file. A later PR adds a cell by adding entries there and files
here (configs/, traffic/, entries/, references/, layer_metrics/); nothing in
this module knows any cell, configuration, mix, rule or metric by name."""

from __future__ import annotations

import importlib.util
import json
import os
import re

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)

NAME_RX = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT_RX = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")
TRAFFIC_SUFFIXES = (".json", ".jsonl", ".toml", ".txt", ".csv")


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_benchmark(root: str = ROOT) -> dict:
    return load_json(os.path.join(root, "BENCHMARK.json"))


def _by_name(entries, name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise KeyError(f"BENCHMARK.json has no {what} named {name!r}")


def traffic_path(here: str, name: str) -> str:
    return os.path.join(here, "traffic", name + ".json")


def module_path(here: str, kind: str, name: str) -> str:
    """entries/<name>.py, references/<name>.py or layer_metrics/<name>.py."""
    return os.path.join(here, kind, name + ".py")


def load_module(path: str):
    """A file found by name; its name may hold dots, so it is no package
    import."""
    spec = importlib.util.spec_from_file_location(
        "bench_" + re.sub(r"\W", "_", os.path.basename(path)[:-3]), path
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metrics_of(bm: dict, kind: str, cell: str) -> list:
    """The metrics of `kind` (end_to_end / per_layer) that `cell` reports: a
    metric with no `workloads` key belongs to every cell that reports the
    end-to-end metric it is or moves."""
    e2e = [m for m in bm["end_to_end"] if cell in m.get("workloads", [cell])]
    if kind == "end_to_end":
        return e2e
    names = {m["name"] for m in e2e}
    return [
        m for m in bm["per_layer"]
        if cell in m.get("workloads", [cell]) and m["moves"] in names
    ]


class Cell:
    """One entry of `workloads` with its configuration and traffic files."""

    def __init__(self, bm: dict, name: str, root: str = ROOT, here: str = HERE):
        self.bm = bm
        self.root = root
        self.here = here
        self.workload = _by_name(bm["workloads"], name, "workload")
        self.name = name
        self.chips = int(self.workload["chips"])
        cfg_entry = _by_name(bm["configs"], self.workload["config"], "config")
        self.config = load_json(os.path.join(root, cfg_entry["file"]))
        self.traffic = load_json(traffic_path(here, self.workload["traffic"]))
        self.end_to_end = metrics_of(bm, "end_to_end", name)
        self.per_layer = metrics_of(bm, "per_layer", name)

    def entry(self):
        return load_module(module_path(self.here, "entries", self.traffic["entry"]))

    def rule(self):
        """The verdict rule the deployment promises, as
        `verdict(mask, signers, powers, total_power, blocks) -> str`: the
        configuration's `verdict_rule`, found as references/<name>.py, or
        where it names none, VerifyCommit (reference.verdict)."""
        name = self.config.get("verdict_rule")
        path = module_path(self.here, "references", name) if name else \
            os.path.join(self.here, "reference.py")
        return load_module(path).verdict

    def reader(self, metric_name: str):
        return load_module(module_path(self.here, "layer_metrics", metric_name))


def _chain_faults(cfg: dict) -> list:
    """What a configuration's chain keys (`headers`,
    `validator_changes_per_height`, `fresh_voting_powers`) have to be."""
    bad = []
    if type(cfg.get("headers", False)) is not bool:
        bad.append(f"headers {cfg['headers']!r} is not true or false")
    k = cfg.get("validator_changes_per_height", 0)
    n = cfg.get("validators")
    if type(k) is not int or k < 0 or (type(n) is int and k > n):
        bad.append(f"validator_changes_per_height {k!r} is not a whole number from 0 to validators")
    elif k and cfg.get("headers") is not True:
        bad.append("validator_changes_per_height needs headers: without them a set has no hash "
                   "to be committed to")
    if "fresh_voting_powers" in cfg:
        fresh = cfg["fresh_voting_powers"]
        if not (isinstance(fresh, list) and fresh
                and all(type(p) is int and p > 0 for p in fresh)):
            bad.append("fresh_voting_powers is not a list of whole numbers over 0")
        elif not (type(k) is int and k > 0):
            bad.append("fresh_voting_powers needs validator_changes_per_height over 0")
    return bad


def lint(bm: dict, root: str = ROOT, here: str = HERE) -> list:
    """What selftest.py holds BENCHMARK.json to; returns the faults found."""
    bad = []

    def name_ok(s, where):
        if not isinstance(s, str) or not NAME_RX.match(s):
            bad.append(f"{where}: {s!r} is not a name")

    want = {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"}
    if set(bm) != want:
        bad.append(f"top-level keys {sorted(bm)} != {sorted(want)}")
        return bad
    if not (isinstance(bm["run_seconds"], int) and 1 <= bm["run_seconds"] <= 51):
        bad.append("run_seconds is not a whole number from 1 to 51")
    paths = bm["paths"]
    under = lambda p: any(p == d or p.startswith(d + "/") for d in paths)
    for word in bm["command"]:
        if word.startswith("/") or ".." in word.split("/"):
            bad.append(f"command word {word!r} leaves the repo")
    cfg_names, files, rules, chains = set(), set(), {}, set()
    for c in bm["configs"]:
        if set(c) != {"name", "source", "file", "reduced", "why"}:
            bad.append(f"config {c.get('name')}: keys {sorted(c)}")
            continue
        name_ok(c["name"], "config")
        if c["name"] in cfg_names or c["file"] in files:
            bad.append(f"config {c['name']}: name or file used twice")
        cfg_names.add(c["name"])
        files.add(c["file"])
        if not under(c["file"]) or not os.path.isfile(os.path.join(root, c["file"])):
            bad.append(f"config {c['name']}: file {c['file']} missing or outside paths")
        else:
            cfg = load_json(os.path.join(root, c["file"]))
            rules[c["name"]] = cfg.get("verdict_rule")
            if "voting_powers" in cfg and len(cfg["voting_powers"]) != cfg.get("validators"):
                bad.append(f"config {c['name']}: {len(cfg['voting_powers'])} voting_powers "
                           f"for {cfg.get('validators')} validators")
            bad += [f"config {c['name']}: {fault}" for fault in _chain_faults(cfg)]
            if cfg.get("headers"):
                chains.add(c["name"])
            if "verdict_rule" in cfg:
                name_ok(cfg["verdict_rule"], f"config {c['name']} verdict_rule")
                if not os.path.isfile(module_path(here, "references", str(cfg["verdict_rule"]))):
                    bad.append(f"config {c['name']}: no rule file "
                               f"references/{cfg['verdict_rule']}.py")
        for k in c["reduced"]:
            name_ok(k, f"config {c['name']} reduced")
        for k in ("source", "why"):
            if not (1 <= len(c[k]) <= 200) or "\n" in c[k] or "\t" in c[k]:
                bad.append(f"config {c['name']}: {k} is not one line of 1-200 characters")
    cells, pairs, used = set(), set(), set()
    for w in bm["workloads"]:
        if set(w) != {"name", "config", "traffic", "chips", "why"}:
            bad.append(f"workload {w.get('name')}: keys {sorted(w)}")
            continue
        for k in ("name", "config", "traffic"):
            name_ok(w[k], f"workload {k}")
        if w["name"] in cells or (w["config"], w["traffic"]) in pairs:
            bad.append(f"workload {w['name']}: name or (config, traffic) used twice")
        cells.add(w["name"])
        pairs.add((w["config"], w["traffic"]))
        used.add(w["config"])
        if w["config"] not in cfg_names:
            bad.append(f"workload {w['name']}: no config {w['config']}")
        if w["chips"] not in (1, 4):
            bad.append(f"workload {w['name']}: chips {w['chips']}")
        if not (1 <= len(w["why"]) <= 200) or "\n" in w["why"] or "\t" in w["why"]:
            bad.append(f"workload {w['name']}: why is not one line of 1-200 characters")
        tp = traffic_path(here, w["traffic"])
        if not os.path.isfile(tp):
            bad.append(f"workload {w['name']}: no traffic file {tp}")
        else:
            mix = load_json(tp)
            if not os.path.isfile(module_path(here, "entries", mix.get("entry", ""))):
                bad.append(f"traffic {w['traffic']}: no entry driver {mix.get('entry', '')!r}")
            per_call = mix.get("commits_per_call", 1)
            if type(per_call) is not int or per_call < 1:
                bad.append(f"traffic {w['traffic']}: commits_per_call {per_call!r} is not a "
                           "whole number from 1")
            elif per_call > 1 and not rules.get(w["config"]):
                bad.append(f"workload {w['name']}: a call of {per_call} commits needs the "
                           "configuration to name its verdict_rule")
            if w["config"] in chains:
                if not rules.get(w["config"]):
                    bad.append(f"workload {w['name']}: a chain of signed headers needs the "
                               "configuration to name its verdict_rule")
                first = mix.get("first_height", 1)
                if type(first) is not int or first < 2:
                    bad.append(f"traffic {w['traffic']}: first_height {first!r}: a chain's trusted "
                               "root stands one height under it, so it is 2 or more")
    if cfg_names - used:
        bad.append(f"configs used by no cell: {sorted(cfg_names - used)}")
    four = sum(1 for w in bm["workloads"] if w.get("chips") == 4)
    if four > max(1, len(bm["workloads"]) // 2):
        bad.append(f"{four} of {len(bm['workloads'])} cells ask for 4 chips")
    seen = set()
    e2e = {}
    for m in bm["end_to_end"]:
        if not set(m) <= {"name", "unit", "better", "bound", "source", "workloads"} or \
                not {"name", "unit", "better", "bound", "source"} <= set(m):
            bad.append(f"end_to_end {m.get('name')}: keys {sorted(m)}")
            continue
        e2e[m["name"]] = m
        if m["source"] not in ("host_clock", "device_trace"):
            bad.append(f"end_to_end {m['name']}: source {m['source']}")
        if not (0.01 <= m["bound"] <= 0.25):
            bad.append(f"end_to_end {m['name']}: bound {m['bound']}")
    if "setup_s" not in e2e:
        bad.append("no end-to-end metric setup_s")
    for m in bm["per_layer"]:
        if not set(m) <= {"name", "unit", "better", "source", "layer", "moves", "workloads"} or \
                not {"name", "unit", "better", "source", "layer", "moves"} <= set(m):
            bad.append(f"per_layer {m.get('name')}: keys {sorted(m)}")
            continue
        if m["moves"] not in e2e:
            bad.append(f"per_layer {m['name']}: moves {m['moves']!r}, no such end-to-end metric")
        if not os.path.isfile(module_path(here, "layer_metrics", m["name"])):
            bad.append(f"per_layer {m['name']}: no reader layer_metrics/{m['name']}.py")
        if m["name"].endswith("_roofline") and m["unit"] != "%":
            bad.append(f"per_layer {m['name']}: a roofline share has the unit %")
    for m in bm["end_to_end"] + bm["per_layer"]:
        name_ok(m.get("name"), "metric")
        if m.get("name") in seen:
            bad.append(f"metric {m.get('name')}: name used twice")
        seen.add(m.get("name"))
        if not UNIT_RX.match(str(m.get("unit", ""))):
            bad.append(f"metric {m.get('name')}: unit {m.get('unit')!r}")
        if m.get("better") not in ("lower", "higher"):
            bad.append(f"metric {m.get('name')}: better {m.get('better')!r}")
        if m.get("source") not in SOURCES:
            bad.append(f"metric {m.get('name')}: source {m.get('source')!r}")
        for w in m.get("workloads", []):
            if w not in cells:
                bad.append(f"metric {m.get('name')}: no cell {w}")
    for cell in cells:
        mine = {m["name"] for m in metrics_of(bm, "end_to_end", cell)}
        if "setup_s" not in mine or len(mine) < 2:
            bad.append(f"cell {cell}: reports {sorted(mine)}, needs setup_s and one more")
        if not metrics_of(bm, "per_layer", cell):
            bad.append(f"cell {cell}: reports no per-layer metric")
        for m in bm["per_layer"]:
            if cell in m.get("workloads", []) and m.get("moves") not in mine:
                bad.append(f"per_layer {m['name']}: cell {cell} does not report {m['moves']}")
    return bad
