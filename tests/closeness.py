"""What two DIFFERENT XLA programs of the same math can promise.

A sharded scan and a single-device scan, a bucket-padded batch and an
unpadded one, a grouped shard_map update and an op-by-op eager one: each pair
computes the same sums in another order or with other vector widths, so the
last bits of a float32 result belong to the compiler, not to the feature.
What the features guarantee — and what these helpers assert — is the same
top-k IDS and values within a few float32 ULP *of the result's scale* (a
D-term dot product's rounding error scales with the operands, not with a
result that happens to cancel towards zero, so per-element ULP distance is
the wrong yardstick).  Where both sides run the SAME program on the same
inputs, tests keep ``assert_array_equal``.
"""

import numpy as np


def assert_within_ulp(got, want, max_ulp: int = 4, err_msg: str = ""):
    """``|got - want| <= max_ulp`` float32 ULPs of ``max|want|``."""
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    assert got.shape == want.shape, (got.shape, want.shape)
    scale = np.float32(max(float(np.abs(want).max(initial=0.0)),
                           float(np.finfo(np.float32).tiny)))
    np.testing.assert_allclose(got, want, rtol=0.0,
                               atol=float(max_ulp * np.spacing(scale)),
                               err_msg=err_msg)


def assert_same_topk(ids, scores, ref_ids, ref_scores, max_ulp: int = 4):
    """Retrieval's contract across programs: identical ids (ties included —
    both programs break them towards the lower id), scores within ULPs."""
    np.testing.assert_array_equal(np.asarray(ids), np.asarray(ref_ids))
    assert np.asarray(scores).dtype == np.float32
    assert_within_ulp(scores, ref_scores, max_ulp)
