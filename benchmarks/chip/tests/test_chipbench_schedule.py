"""The open-loop schedule: deterministic in the seed, the same work for
every seed, Zipf-skewed identities."""
import json
import sys
from collections import Counter
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parents[3]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from benchmarks.chip import harness  # noqa: E402

BENCH = ROOT / "benchmarks" / "chip"
MIX = json.loads((BENCH / "traffic" / "mixed.json").read_text())
open_loop = harness.Bench(ROOT, BENCH).kind("open_loop")
BIG = 2**31 + 12345  # run seeds may exceed 32 signed bits


def _plain(items):
    return [(it["due"], it["kind"], it["num_samples"], it["seed"],
             None if it["xs"] is None else it["xs"].tobytes()) for it in items]


def test_same_seed_same_schedule():
    a = open_loop.schedule(MIX, BIG, 20.0, 26)
    b = open_loop.schedule(MIX, BIG, 20.0, 26)
    assert _plain(a) == _plain(b)
    assert _plain(a) != _plain(open_loop.schedule(MIX, BIG + 1, 20.0, 26))


def test_every_seed_gets_the_same_work():
    runs = [open_loop.schedule(MIX, s, 20.0, 26) for s in (1, 2, BIG)]
    n = round(MIX["rate_per_s"] * 20.0)
    for items in runs:
        assert len(items) == n
        dues = [it["due"] for it in items]
        assert dues == sorted(dues) and 0 < dues[0] and dues[-1] < 20.0
    for key in ("kind", "num_samples"):
        counts = [Counter(it[key] for it in items) for items in runs]
        assert counts[0] == counts[1] == counts[2]
    rows = [sorted(it["xs"].shape[0] for it in items if it["xs"] is not None)
            for items in runs]
    assert rows[0] == rows[1] == rows[2]
    gaps = [sorted(np.diff([0.0] + [it["due"] for it in items]).round(9))
            for items in runs]
    np.testing.assert_allclose(gaps[0], gaps[1])
    kinds = Counter(it["kind"] for it in runs[0])
    assert kinds["predict"] == kinds["sample"]
    assert abs(kinds["predict"] - 2 * kinds["thompson_step"]) <= 1


def test_rows_samples_and_identities_follow_the_mix():
    items = open_loop.schedule(MIX, BIG, 40.0, 26)
    rows = Counter(it["xs"].shape[0] for it in items if it["kind"] == "predict")
    assert set(rows) == set(MIX["predict_rows"])
    assert max(rows.values()) - min(rows.values()) <= 1
    assert {it["xs"].shape[0] for it in items if it["kind"] == "sample"} == {MIX["sample_rows"]}
    assert all(it["xs"].dtype == np.float32 and it["xs"].shape[1] == 26
               for it in items if it["xs"] is not None)
    assert {it["num_samples"] for it in items if it["kind"] != "predict"} == {MIX["samples"]}
    for kind in ("sample", "thompson_step"):
        ids = Counter(it["seed"] for it in items if it["kind"] == kind)
        assert all(open_loop.WINDOW_IDS <= s < open_loop.WINDOW_IDS + 512 for s in ids)
        hot = ids.most_common(1)[0][1]
        assert hot >= 0.1 * sum(ids.values())  # Zipf(1.1): a hot head
        assert len(ids) > 5  # and a tail
    warm = open_loop.schedule(MIX, BIG, 40.0, 26, id_base=open_loop.WARMUP_IDS)
    assert not {it["seed"] for it in items} & {it["seed"] for it in warm} - {None}


def test_listed_sample_sizes_are_stratified():
    mix = dict(MIX, samples=[4, 8, 16], sample_rows=[4, 16, 64])
    runs = [open_loop.schedule(mix, s, 40.0, 26) for s in (3, BIG)]
    for key, pick in (("samples", lambda it: it["num_samples"]),
                      ("sample_rows", lambda it: it["xs"].shape[0])):
        kinds = ("sample",) if key == "sample_rows" else ("sample", "thompson_step")
        counts = [Counter(pick(it) for it in items if it["kind"] in kinds)
                  for items in runs]
        assert counts[0] == counts[1]
        assert set(counts[0]) == set(mix[key])
        assert max(counts[0].values()) - min(counts[0].values()) <= 1
