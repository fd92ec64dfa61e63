"""Model families: a tenant that names no family runs the dense one, the
same run as one that names it; a family file that is missing or lacks a
function stops the run before set-up, naming the file and what it
lacks."""
from __future__ import annotations

import copy
import json
import os
import sys
import time

import pytest

CHIP = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, CHIP)
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import harness  # noqa: E402
from test_chip_rehearsal import BIG_SEED, tiny  # noqa: E402


def naming(family):
    """``tiny``, with every tenant naming ``family``."""
    def scale(config, mix):
        config, mix = tiny(config, mix)
        config = copy.deepcopy(config)
        for t in config["tenants"]:
            t["family"] = family
        return config, mix
    return scale


def run(workload, scale):
    return harness.run(["--workload", workload, "--seed", str(BIG_SEED),
                        "--seconds", "6", "--trace", "0"],
                       t_process=time.perf_counter(), check_device=False,
                       scale=scale)


def last_line(capsys):
    res = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    # host-clock readings differ between any two runs; their names do not
    res["metrics"] = {k: v["unit"] for k, v in res["metrics"].items()}
    return res


@pytest.mark.parametrize("workload", ["danube-4b.chat-fits",
                                      "danube-4b-remap.chat-remap"])
def test_no_family_key_is_the_dense_family(workload, capsys):
    for t in harness.resolve(workload)["config"]["tenants"]:
        assert "family" not in t
    assert run(workload, tiny) == 0
    plain = last_line(capsys)
    assert run(workload, naming("dense")) == 0
    named = last_line(capsys)
    assert plain["correct"] is True and plain["checks"]["sampled_tokens"][
        "value"] > 0
    assert named == plain


@pytest.mark.parametrize("lacks", ["served_gaps", "the file"])
def test_an_incomplete_family_stops_the_run_before_set_up(
        lacks, tmp_path, monkeypatch, capsys):
    with open(os.path.join(CHIP, "families", "dense.py")) as f:
        src = f.read()
    if lacks == "served_gaps":
        src = src.replace("served_gaps = reference.served_gaps\n", "")
        (tmp_path / "broken.py").write_text(src)
    monkeypatch.setattr(harness, "FAMILIES", str(tmp_path))
    monkeypatch.setattr(harness, "build_engine", None)   # set-up never runs
    with pytest.raises(SystemExit) as e:
        run("danube-4b.chat-fits", naming("broken"))
    msg = str(e.value)
    assert str(tmp_path / "broken.py") in msg
    assert ("lacks served_gaps" if lacks == "served_gaps"
            else "no such model family file") in msg
    assert "{" not in capsys.readouterr().out
