"""Work counted from shapes, and the table of published peaks."""

import json
from pathlib import Path

import pytest

from bench import peaks, work

CONFIGS = Path(__file__).resolve().parents[1] / "configs"


def test_unknown_device_kind_raises():
    with pytest.raises(ValueError, match="no published peaks"):
        peaks.peaks("TPU v9 imaginary")
    assert peaks.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9


def test_lanes_of_one_spec_move_the_same_bytes():
    # a 16-island ring of 256 individuals over 20 genes, 16-bit each
    spec = {"problem": "rastrigin:20", "n": 256, "bits_per_var": 16,
            "n_islands": 16, "migrate_every": 16, "gens_per_epoch": 64}
    onehot = dict(spec, sel_lane="onehot", v=20)
    gather = dict(onehot, sel_lane="gather")
    assert (work.spec_launch_bytes(onehot, 16)
            == work.spec_launch_bytes(gather, 16) > 0)


def test_paper_f3_n64_launch_bytes_by_hand():
    # N=64, V=2 uint32 words per population:
    #   x 64*2 + selection LFSRs 2*64 + crossover LFSRs 2*32
    #   + mutation LFSRs 2*64 = 448, read once and written once: 896
    #   best: fitness + 2 genes + generation = 4
    # 900 words = 3600 bytes a population; a full pack of 8: 28800 bytes
    cfg = json.loads((CONFIGS / "paper-f3-n64.json").read_text())
    shape = cfg["reference"]["shape"]
    assert work.launch_bytes(8, shape["n"], shape["v"],
                             cfg["ffm_const_bytes"]) == 28800


def test_populations_from_the_launch_output():
    text = ("%ga_generation_kernel.4 = (u32[8,2,64]{2,1,0:T(2,128)S(1)}, "
            "u32[8,2,64]) custom-call(...)")
    assert work.populations(text, n=64, v=2) == 8
    with pytest.raises(ValueError):
        work.populations(text, n=64, v=3)
