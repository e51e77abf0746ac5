import jax.numpy as jnp
import numpy as np
import pytest
from tests.ops.test_pallas_attention import (build_case,
                                             float32_reference, run_both)


@pytest.mark.parametrize("ps,ppr,hd,maxq", [
    (4, 16, 16, 8),   # engine e2e config
    (4, 4, 16, 8),
    (8, 4, 16, 8),
    (4, 16, 128, 8),
    (8, 4, 128, 8),
])
def test_small(ps, ppr, hd, maxq):
    rng = np.random.default_rng(0)
    case = build_case(rng, seqs=[(5, 5)], page_size=ps, pages_per_req=ppr,
                      num_q_heads=4, num_kv_heads=2, head_dim=hd,
                      max_q=maxq)
    got, want = run_both(case)
    np.testing.assert_allclose(got, want, rtol=2e-3, atol=2e-3)


def test_small_bf16_matches_float32_reference():
    """The general kernel on bf16 Q and pages, held to a float32
    reference of the same bf16 inputs (the decode kernel's:
    ``test_pallas_attention.py``, ``test_inactive_rows_and_bf16``)."""
    rng = np.random.default_rng(4)
    case = build_case(rng, seqs=[(5, 21), (3, 3)], page_size=16,
                      pages_per_req=2, num_q_heads=4, num_kv_heads=2,
                      head_dim=128, max_q=8, dtype=jnp.bfloat16)
    got, _ = run_both(case)
    assert got.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.float32(got), float32_reference(case),
                               rtol=3e-2, atol=3e-2)
