"""The ``plan_book_reuse_share`` reader: nothing from closes whose
``parallel`` phase lacks the planner's book counts (a node without the
offer memo), and the share over the window's closes when they are
there."""
import importlib.util
import os

import pytest

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def reader():
    path = os.path.join(HERE, "metrics", "plan_book_reuse_share.py")
    spec = importlib.util.spec_from_file_location("reader", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


PARENT = {"txs": 1000, "clusters": 1, "mode": "parallel"}


@pytest.mark.parametrize("phases,share", [
    # the parent: no counts, no number
    ([{"parallel": PARENT}, {"parallel": dict(PARENT)}, {}], None),
    # plans with no book rows give nothing to share
    ([{"parallel": dict(PARENT, book_rows=0, book_rows_reused=0)}], None),
    # summed over closes, a replayed close without phases skipped
    ([{"parallel": dict(PARENT, book_rows=1000, book_rows_reused=0)},
      {},
      {"parallel": dict(PARENT, book_rows=3000, book_rows_reused=2900)},
      {"plan": 3.0}], 72.5),
], ids=["parent", "no-book", "summed"])
def test_plan_book_reuse_share(phases, share):
    got = reader().read({"closes": [{"phases": p} for p in phases],
                         "spans": [], "profile": None})
    assert got == share
