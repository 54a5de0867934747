"""The readers of the grouped products' time and share
(``grouped_product_ms_per_step`` / ``_roofline_pct``) on made-up records:
a ``by_op`` with XLA's ``ragged-dot-none`` alone (the program before
``ops/grouped_matmul.py``: what ``expert_gmm_*`` read), with the repo's
``pbtpu_gmm`` / ``pbtpu_tgmm`` alone (the same work under the new pair),
and with neither."""

import pytest

from benchmark.metrics import expert_gmm_ms_per_step as old_ms
from benchmark.metrics import expert_gmm_roofline_pct as old_pct
from benchmark.metrics import grouped_product_ms_per_step as ms
from benchmark.metrics import grouped_product_roofline_pct as pct
from benchmark.tests.test_trace_reduce import _route_record

# beside the products: their metadata (the route's), a kernel of another
# metric, the head
OTHERS = {"ragged-dot-metadata.2 s32[16]": 0.001,
          "fusion.4 s32[208]": 0.002,
          "pbtpu_attention_fwd.1 bf16[2,28,8192,128]": 0.4,
          "fusion.1 f32[2560,37984]": 0.7}
BY_OP = {
    "xla": {"ragged-dot-none.7 f32[9856,768]": 0.06,
            "ragged-dot-none.8 f32[6144,2560]": 0.03,
            "ragged-dot-none.9 f32[16,2560,768]": 0.0207},
    "pair": {"pbtpu_gmm.7 f32[9856,768]": 0.03,
             "pbtpu_gmm.12 bf16[6144,2560]": 0.02,
             "pbtpu_tgmm.3 bf16[16,2560,768]": 0.0207},
    "neither": {},
}


@pytest.mark.parametrize("ran,seconds,share", [
    ("xla", 0.1107, 16.0), ("pair", 0.0707, 25.0), ("neither", None, None)])
def test_the_products_are_read_under_either_implementation(ran, seconds,
                                                           share):
    record = _route_record("smallthinker_21b_ep4",
                           {**OTHERS, **BY_OP[ran]}, steps=1)
    if seconds is None:
        assert ms.read(record) is None and pct.read(record) is None
        return
    assert ms.read(record) == pytest.approx(1e3 * seconds)
    assert round(pct.read(record), 1) == share
    # the readers they take over from find XLA's kernels alone
    if ran == "xla":
        assert old_ms.read(record) == pytest.approx(ms.read(record))
        assert old_pct.read(record) == pytest.approx(pct.read(record))
    else:
        assert old_ms.read(record) is None and old_pct.read(record) is None
    # a record of no listed cell reads the time and no share; no trace,
    # nothing
    other = {**record, "work": {"flops": 1.0}}
    assert ms.read(other) == pytest.approx(1e3 * seconds)
    assert pct.read(other) is None
    assert ms.read({**record, "trace": None}) is None
    assert pct.read({**record, "trace": None}) is None
