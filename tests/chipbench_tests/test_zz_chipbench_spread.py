"""The spread tool reads a set of runs as the benchmark's contract does."""
import json
import os
import statistics

import pytest

from chipbench import catalog, spread


def _set(name):
    path = os.path.join(catalog.ROOT, "chipbench", "results", "pr23", name)
    with open(path) as f:
        return [json.loads(line) for line in f]


def test_spread_is_the_quartiles_of_statistics_quantiles():
    values = [100.0, 101.0, 103.0, 104.0, 108.0, 90.0]
    q1, _, q3 = statistics.quantiles(values, n=4)
    assert spread.spread(values) == (q3 - q1) / statistics.median(values)
    # numpy's default quartiles lie closer together and must not be these
    assert spread.spread(values) == pytest.approx((105.0 - 97.5) / 102.0)


@pytest.mark.parametrize("metric,whole,without", [
    ("tokens_per_s_per_chip", 0.021669, 0.002624),
    ("step_ms_p90", 0.001527, 0.001164)])
def test_one_far_run_widens_a_set_and_is_left_out_for_tightness(
        metric, whole, without):
    # PR 23's set 2 of gpt2s-b16: one run of six read 7.6 % low
    read = spread.summarize(_set("gpt2s-b16.set2.jsonl"))[metric]
    assert read["runs"] == 6
    assert read["spread"] == pytest.approx(whole, rel=1e-3)
    assert read["spread_without_farthest"] == pytest.approx(without, rel=1e-3)


def test_leaving_a_run_out_never_widens():
    values = [1.0, 2.0, 3.0, 4.0, 5.0, 6.0]
    assert spread.spread_without_farthest(values) <= spread.spread(values)
