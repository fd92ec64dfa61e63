"""CPU rehearsal of the chip benchmark: name resolution and the rule by
which a cell gets its per-layer metrics, seed-stable traffic, a well-formed
result line from a tiny run through the test-only entry, the real entry's
refusal without a TPU, and a cell added as files only."""
from __future__ import annotations

import copy
import json
import os
import re
import shutil
import subprocess
import sys
import time

import numpy as np
import pytest

CHIP = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(os.path.dirname(CHIP))
sys.path.insert(0, CHIP)

import gen  # noqa: E402
import harness  # noqa: E402

BIG_SEED = 2 ** 31 + 12345


def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def tiny(config, mix):
    """Same code path, CPU-sized: 2 layers at small widths, short prompts."""
    config, mix = copy.deepcopy(config), copy.deepcopy(mix)
    for t in config["tenants"]:
        t["arch"].update(hidden_size=64, intermediate_size=128,
                         num_hidden_layers=2, num_attention_heads=4,
                         num_key_value_heads=2, head_dim=16, vocab_size=256)
        t["max_batch"], t["max_context"] = 4, 256
    config["engine"].update(base_kv_pages=24, page_size=16,
                            prefill_chunk_tokens=64)
    for s in mix["streams"]:
        key = "median" if "median" in s["prompt"] else "mean"
        s["prompt"].update({key: 60, "min": 4, "max": 180})
        s["output"].update(max=12)
    mix["preroll"]["requests"] = 3
    if config.get("setup_remap_alpha"):
        # two layers leave room to donate one
        config["setup_remap_alpha"] = {
            k: 1 for k in config["setup_remap_alpha"]}
        config["engine"]["runtime"]["max_remap_fraction"] = 0.5
    return config, mix


def check_contract(bench: dict) -> list:
    """Every breach, in ``bench``, of the rule by which the harness measures
    a cell: by the per-layer metrics whose ``workloads`` list names it, or
    that have no list, and by no others. Files are found through the
    ``harness`` beside this file, so a copy of the benchmark checks the
    copy's files."""
    out = []
    cells = {w["name"] for w in bench["workloads"]}
    for m in bench["per_layer"]:
        listed = m.get("workloads")
        if listed is None:
            continue
        out += [f"{m['name']}: workloads names {w!r}, which is no cell"
                for w in listed if w not in cells]
        if not cells & set(listed):
            out.append(f"{m['name']}: no cell reads it")
    confs = {c["name"]: c for c in bench["configs"]}
    for w in bench["workloads"]:
        name, found = w["name"], len(out)
        conf = confs.get(w["config"])
        if conf is None:
            out.append(f"{name}: no configuration {w['config']!r}")
        elif not os.path.isfile(os.path.join(harness.ROOT, conf["file"])):
            out.append(f"{name}: no configuration file {conf['file']}")
        if not os.path.isfile(os.path.join(harness.HERE, "traffic",
                                           w["traffic"] + ".json")):
            out.append(f"{name}: no traffic file {w['traffic']}.json")
        if len(out) > found:
            continue
        try:
            info = harness.resolve(name, bench)
        except SystemExit as e:         # a model family missing or partial
            out.append(f"{name}: {e}")
            continue
        got = sorted(m["name"] for m in info["per_layer"])
        want = sorted(m["name"] for m in bench["per_layer"]
                      if "workloads" not in m or name in m["workloads"])
        if got != want:
            out.append(f"{name}: resolves {got}, but {want} name it")
        if not got:
            out.append(f"{name}: resolves no per-layer metric")
        e2e = {m["name"] for m in info["end_to_end"]}
        lacks = {"setup_s", "tokens_per_s"} - e2e
        if lacks:
            out.append(f"{name}: no end-to-end {sorted(lacks)}")
        for m in info["per_layer"]:
            if m["moves"] not in e2e:
                out.append(f"{name}: {m['name']} moves {m['moves']!r}, "
                           f"which is no end-to-end metric of the cell")
            try:
                read = harness.load_reader(m["name"])
            except (OSError, ImportError, SyntaxError, AttributeError) as e:
                out.append(f"{name}: reader of {m['name']} does not load: "
                           f"{e!r}")
                continue
            if not callable(read):
                out.append(f"{name}: reader of {m['name']} has no read()")
    return out


def test_every_cell_resolves_by_name():
    b = bench()
    assert b["paths"] == ["benchmarks/chip"]
    assert check_contract(b) == []
    for w in b["workloads"]:
        info = harness.resolve(w["name"], b)
        assert info["config"]["tenants"] and info["mix"]["streams"]


def unread(b):
    """No metric lists the first cell any more."""
    for m in b["per_layer"]:
        m["workloads"] = [w for w in m["workloads"]
                          if w != b["workloads"][0]["name"]]


FAULTS = {
    "metric names no cell": (
        lambda b: b["per_layer"][0]["workloads"].append("no-such.cell"),
        "workloads names 'no-such.cell', which is no cell"),
    "metric no cell reads": (
        lambda b: b["per_layer"][1].update(workloads=[]),
        "no cell reads it"),
    "reader file missing": (
        lambda b: b["per_layer"].append(
            dict(b["per_layer"][2], name="no_such_reader")),
        "reader of no_such_reader does not load"),
    "moves no end-to-end metric": (
        lambda b: b["per_layer"][3].update(moves="tbt_p95_s"),
        "moves 'tbt_p95_s', which is no end-to-end metric"),
    "cell reads no metric": (
        unread, "resolves no per-layer metric"),
    "traffic file missing": (
        lambda b: b["workloads"][1].update(traffic="no-such-mix"),
        "no traffic file no-such-mix.json"),
}


@pytest.mark.parametrize("fault", list(FAULTS))
def test_the_contract_check_reports_each_fault(fault):
    b = bench()
    plant, says = FAULTS[fault]
    plant(b)
    breaches = check_contract(b)
    assert any(says in line for line in breaches), breaches


def test_configs_name_their_source_and_cuts():
    for c in bench()["configs"]:
        with open(os.path.join(ROOT, c["file"])) as f:
            conf = json.load(f)
        assert conf["source"] == c["source"]
        assert sorted(conf["reduced"]) == sorted(c["reduced"])
        assert conf["deployment"] and conf["limits"]["max_logit_gap"] > 0
        for t in conf["tenants"]:
            assert t["source"] and "assumed" in t


@pytest.mark.parametrize("traffic", ["chat-fits", "chat-remap"])
def test_traffic_is_seed_stable(traffic):
    mix = harness.load_json(CHIP, "traffic", traffic + ".json")
    vocab = {s["tenant"]: 32000 for s in mix["streams"]}
    a = gen.generate(mix, BIG_SEED, 50, vocab)
    b = gen.generate(mix, BIG_SEED, 50, vocab)
    c = gen.generate(mix, 7, 50, vocab)
    assert [(x.rid, x.due, x.max_new) for x in a] == \
        [(x.rid, x.due, x.max_new) for x in b]
    assert all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))
    # another seed: the same work in another order
    assert sorted(len(x.prompt) for x in a) == sorted(len(x.prompt) for x in c)
    assert sorted(x.max_new for x in a) == sorted(x.max_new for x in c)
    assert [len(x.prompt) for x in a] != [len(x.prompt) for x in c]
    win = [x for x in a if not x.preroll]
    assert win and all(0 <= x.due < 50 for x in win)
    pre = [x for x in a if x.preroll]
    assert len(pre) == mix["preroll"]["requests"]
    assert all(x.due < 0 for x in pre)
    assert gen.chunk_shapes(a, 256) == gen.chunk_shapes(c, 256)


def tiny_run(workload, capsys):
    rc = harness.run(["--workload", workload, "--seed",
                      str(BIG_SEED), "--seconds", "6", "--trace", "0"],
                     t_process=time.perf_counter(), check_device=False,
                     scale=tiny)
    assert rc == 0
    out = capsys.readouterr()
    res = json.loads(out.out.strip().splitlines()[-1])
    assert set(res) >= {"correct", "attempted", "failed", "metrics",
                        "device"}
    assert list(res)[-1] == "checks"
    assert res["correct"] is True
    assert res["attempted"] > 0 and res["failed"] == 0
    m = res["metrics"]
    assert set(m) == {e["name"] for e in harness.resolve(
        workload)["end_to_end"]}
    assert all(v["value"] > 0 for v in m.values())
    assert res["device"]["platform"] == "cpu"
    assert out.err.strip().splitlines()[-1].startswith("check ")
    calls = re.search(r"(\d+) decode calls \((\d+) remapped\)", out.err)
    return int(calls[1]), int(calls[2])


def test_tiny_run_prints_a_well_formed_last_line(capsys):
    n, remapped = tiny_run("danube-4b.chat-fits", capsys)
    assert n > 0 and remapped == 0


def test_tiny_remap_run_decodes_on_donated_layers(capsys):
    """Set-up donates the configured layers, and the window holds that
    tier: every decode call streams layers from host memory."""
    n, remapped = tiny_run("danube-4b-remap.chat-remap", capsys)
    assert n > 0 and remapped == n


def test_real_entry_refuses_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(CHIP, "run.py"), "--workload",
         "danube-4b.chat-fits", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=ROOT, env=env, capture_output=True,
        text=True, timeout=120)
    assert p.returncode != 0
    assert "{" not in p.stdout


def test_run_outside_the_repository_fails(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(CHIP, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    p = subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload",
         "danube-4b.chat-fits", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=tmp_path, env=env, capture_output=True,
        text=True, timeout=120)
    assert p.returncode != 0
    assert "{" not in p.stdout


ADDED = r'''
import json, os, sys
chip = os.path.join(sys.argv[1], "benchmarks", "chip")
sys.path[:0] = [chip, os.path.join(chip, "tests")]
import gen, harness
from test_chip_rehearsal import check_contract
info = harness.resolve("danube-4b.new-mix")
vocab = {s["tenant"]: 32000 for s in info["mix"]["streams"]}
items = gen.generate(info["mix"], 3, 10, vocab)
reader = harness.load_reader("new_metric")
print(json.dumps({"config": info["config"]["name"], "n": len(items),
                  "metrics": [m["name"] for m in info["per_layer"]],
                  "value": reader(None),
                  "contract": check_contract(
                      harness.load_json(sys.argv[1], "BENCHMARK.json"))}))
'''


def test_a_cell_config_and_metric_are_added_as_files_only(tmp_path):
    """A new configuration, traffic mix and per-layer metric: new files and
    new BENCHMARK.json entries, with no edit to any file already there."""
    shutil.copytree(CHIP, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = {p: (tmp_path / p).read_bytes() for p in
              [os.path.relpath(os.path.join(d, f), tmp_path)
               for d, _, fs in os.walk(tmp_path) for f in fs]}
    b = bench()
    chip = tmp_path / "benchmarks" / "chip"
    conf = json.loads((chip / "configs" / "danube-4b.json").read_text())
    conf["name"] = "danube-4b-copy"
    (chip / "configs" / "danube-4b-copy.json").write_text(json.dumps(conf))
    mix = json.loads((chip / "traffic" / "chat-fits.json").read_text())
    mix["name"], mix["rate_rps"] = "new-mix", 0.5
    (chip / "traffic" / "new-mix.json").write_text(json.dumps(mix))
    (chip / "metrics" / "new_metric.py").write_text(
        "def read(r):\n    return 42.0\n")
    b["configs"].append(dict(b["configs"][0], name="danube-4b-copy",
                             file="benchmarks/chip/configs/"
                                  "danube-4b-copy.json"))
    b["workloads"].append({"name": "danube-4b.new-mix",
                           "config": "danube-4b-copy", "traffic": "new-mix",
                           "chips": 1, "why": "added as files only"})
    b["per_layer"].append({"name": "new_metric", "unit": "%",
                           "better": "higher", "source": "program_counter",
                           "layer": "model step", "moves": "tokens_per_s",
                           "workloads": ["danube-4b.new-mix"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(b))
    for p, data in before.items():
        assert (tmp_path / p).read_bytes() == data, p
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "-c", ADDED, str(tmp_path)],
                       env=env, capture_output=True, text=True, timeout=120)
    assert p.returncode == 0, p.stderr
    res = json.loads(p.stdout.strip().splitlines()[-1])
    assert res == {"config": "danube-4b-copy", "n": res["n"],
                   "metrics": ["new_metric"], "value": 42.0, "contract": []}
    assert res["n"] > 0


# the dense family under a new name, with weights of its own (those of the
# next seed), so that a run whose engine or comparison went round the
# family would serve one set of weights and judge by another
DENSE2 = '''

def make(arch, seed, tenant_index):
    return weights.make(arch, seed + 1, tenant_index)
'''
# and, for the second case, readings that only this family gives
DENSE2_OFF = '''

def served_gaps(w, arch, prompt, served):
    return reference.served_gaps(w, arch, prompt, served) + 1.0


def control_gaps(w, arch, prompt, served):
    return 0.0 * reference.control_gaps(w, arch, prompt, served) + 7.0
'''

FAMILY_RUN = r'''
import json, os, sys, time
chip = os.path.join(sys.argv[1], "benchmarks", "chip")
sys.path[:0] = [chip, os.path.join(chip, "tests")]
import calibrate, harness
from test_chip_rehearsal import BIG_SEED, check_contract, tiny
print("contract " + json.dumps(check_contract(
    harness.load_json(sys.argv[1], "BENCHMARK.json"))), file=sys.stderr)
compare, control = harness.compare, {}

def both(config, seed, chosen):
    out = compare(config, seed, chosen)
    control.update(calibrate.control_reading(config, seed, chosen))
    return out

harness.compare = both
rc = harness.run(["--workload", "danube-4b.dense2-chat", "--seed",
                  str(BIG_SEED), "--seconds", "6", "--trace", sys.argv[2]],
                 t_process=time.perf_counter(), check_device=False,
                 scale=tiny)
print("control " + json.dumps(control), file=sys.stderr)
sys.exit(rc)
'''


@pytest.mark.parametrize("off", [False, True], ids=["sound", "gaps_off"])
def test_a_model_family_is_added_as_files_only(tmp_path, off):
    """A new model family, a configuration that names it, a cell and a
    metric: new files and new BENCHMARK.json entries, with no edit to any
    file already there. The run builds the engine, compares and reads the
    control through that family, and hands it to the metric's reader."""
    shutil.copytree(CHIP, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = {p: (tmp_path / p).read_bytes() for p in
              [os.path.relpath(os.path.join(d, f), tmp_path)
               for d, _, fs in os.walk(tmp_path) for f in fs]}
    b = bench()
    chip = tmp_path / "benchmarks" / "chip"
    family = (chip / "families" / "dense.py").read_text() + DENSE2
    (chip / "families" / "dense2.py").write_text(
        family + (DENSE2_OFF if off else ""))
    conf = json.loads((chip / "configs" / "danube-4b.json").read_text())
    conf["name"] = "danube-4b-dense2"
    conf["tenants"][0]["family"] = "dense2"
    (chip / "configs" / "danube-4b-dense2.json").write_text(json.dumps(conf))
    (chip / "metrics" / "dense2_family.py").write_text(
        "import harness\n\n\ndef read(r):\n"
        "    want = harness.load_family('dense2')\n"
        "    return 1.0 if r.families and all(\n"
        "        f is want for f in r.families.values()) else 0.0\n")
    b["configs"].append(dict(b["configs"][0], name="danube-4b-dense2",
                             file="benchmarks/chip/configs/"
                                  "danube-4b-dense2.json"))
    b["workloads"].append({"name": "danube-4b.dense2-chat",
                           "config": "danube-4b-dense2",
                           "traffic": "chat-fits", "chips": 1,
                           "why": "a model family added as files only"})
    b["per_layer"].append({"name": "dense2_family", "unit": "count",
                           "better": "higher", "source": "program_counter",
                           "layer": "model step", "moves": "tokens_per_s",
                           "workloads": ["danube-4b.dense2-chat"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(b))
    for p, data in before.items():
        assert (tmp_path / p).read_bytes() == data, p
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.join(ROOT, "src"))
    p = subprocess.run(
        [sys.executable, "-c", FAMILY_RUN, str(tmp_path), str(int(not off))],
        env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode == 0, p.stderr[-4000:]
    res = json.loads(p.stdout.strip().splitlines()[-1])
    assert set(res) >= {"correct", "attempted", "failed", "metrics",
                        "device"}
    assert list(res)[-1] == "checks"
    gap = res["checks"]["max_logit_gap"]
    assert json.loads(p.stderr.split("contract ")[-1].splitlines()[0]) == []
    control = json.loads(p.stderr.split("control ")[-1].splitlines()[0])
    if off:
        assert res["correct"] is False
        # the family's own gaps: the served tokens are its weights' argmax,
        # so each reads its offset of 1.0 and little more
        assert 1.0 <= gap["value"] < 1.0 + gap["limit"]
        assert control["control_max_logit_gap"] == 7.0
    else:
        assert res["correct"] is True
        assert res["metrics"] == {"dense2_family": {"value": 1.0,
                                                    "unit": "count"}}
        assert control["control_tokens"] == res["checks"][
            "sampled_tokens"]["value"]
