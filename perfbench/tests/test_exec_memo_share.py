"""The exec_memo_share reader on fabricated runs."""

import pytest

import harness


class FakeCell:
    n = 1000
    chips = 1
    cfg = {"run": {"target_coverage": 0.99, "max_rounds": 128}}


def fake_run(metas):
    sims = [harness.Sim(i, 100 + i, 2.0 * i, 2.0 * i + 2.0,
                        None if meta is None else
                        {"rounds": 30, "coverage": 0.995, "msgs": 6e4,
                         "wall_s": 1.5, "meta": meta}, None)
            for i, meta in enumerate(metas)]
    return harness.Run(FakeCell(), sims, 12.0, 0, None, {})


def read(run):
    return harness.load_module("metrics", "exec_memo_share.py").read(run)


@pytest.mark.parametrize("metas,share", [
    ([{"exec_memo": True}] * 3, 1.0),
    ([{"exec_memo": False}] * 3, 0.0),
    ([{"exec_memo": True}, {"exec_memo": False}, {"exec_memo": True},
      {"exec_memo": True}], 0.75),
    # a failed simulation has no report and is left out
    ([{"exec_memo": True}, None], 1.0)])
def test_share_of_memo_served_reports(metas, share):
    assert read(fake_run(metas)) == pytest.approx(share)


@pytest.mark.parametrize("metas", [
    [{}, {"compile_s": 0.5}],         # a program without the memo
    [None, None],                     # no report at all
    []])
def test_nothing_to_read(metas):
    assert read(fake_run(metas)) is None
