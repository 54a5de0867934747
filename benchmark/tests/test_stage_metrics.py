"""The readers of the program's stage timers (``head``, ``unique_keys``,
``preplan``, ``h2d``, ``close``), each on a made-up record — and on a
record of a program from before those stages, where each reads nothing
and the result line leaves the metric out."""

import importlib

import pytest


def reader(name):
    return importlib.import_module(f"benchmark.metrics.{name}").read


def a_pass(steps, boundary_s, **timers):
    return {"steps": steps, "boundary_s": boundary_s, "timers": timers}


NEW = {"passes": [
    a_pass(224, 0.5, read=0.4, head=1.9, unique_keys=0.9, preplan=0.0,
           h2d=0.448, close=0.2),
    a_pass(224, 0.7, read=0.5, head=2.1, unique_keys=0.9, preplan=0.1,
           h2d=0.224, close=0.4)]}
OLD = {"passes": [a_pass(224, 0.5, read=0.4, translate=3.0, train=1.0,
                         auc=7.0, drain=0.1)]}

EXPECTED = {
    "pass_head_s_per_pass": 2.0,
    "unique_keys_s_per_pass": 0.9,
    # (1.9 - 0.9 - 0.0 - 0.5 + 2.1 - 0.9 - 0.1 - 0.7) / 2
    "first_batch_s_per_pass": 0.45,
    "h2d_stage_ms_per_step": 1.5,          # 0.672 s over 448 steps
    "pass_close_s_per_pass": 0.3,
}


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_reader_on_a_made_up_record(name):
    assert reader(name)(NEW) == pytest.approx(EXPECTED[name])


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_reader_finds_nothing_in_an_older_program(name):
    assert reader(name)(OLD) is None
    assert reader(name)({"passes": []}) is None


def test_the_accepted_pack_reader_still_reads_read():
    assert reader("read_wait_s_per_pass")(NEW) == pytest.approx(0.45)
