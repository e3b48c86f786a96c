"""The paged decode kernel's loop trips in interpret mode: a trip that
serves two items of a program's work list against the kernel whose trip
serves one, bit for bit (``tests/pallas_cases.py TRIP_CASES``)."""

import numpy as np
import pytest

from tests.pallas_cases import TRIP_CASES, trip_case
from vgate_tpu.ops.pallas.paged_attention import paged_decode_attention_pallas


@pytest.mark.fast  # tier-1: what a served decode step's trips hold
@pytest.mark.parametrize("case", list(TRIP_CASES))
def test_decode_kernel_two_items_a_trip_give_one_items_bits(case):
    """A loop trip that serves TWO items of the work list against the
    kernel whose trip serves one (the program as it was): attention and
    both written pools BIT for bit, in the served arithmetic (bf16 pages,
    float32 accumulation, the softmax weights as two bf16 terms)."""
    args, kw, pattern = trip_case(case)
    one, two = (
        paged_decode_attention_pallas(*args, interpret=True, items=items, **kw)
        for items in (1, 2)
    )
    for got, want in zip(two, one):
        np.testing.assert_array_equal(
            np.asarray(got, np.float32), np.asarray(want, np.float32)
        )
    # and the row is where a scatter would have put it
    pool, page_tables = np.asarray(two[1], np.float32), args[3]
    if "layer" in kw:
        pool = pool[int(kw["layer"])]
    ps = pool.shape[-2]
    for b, length in pattern.items():
        if length:
            page = int(page_tables[b, (length - 1) // ps])
            np.testing.assert_array_equal(
                pool[:, page, (length - 1) % ps],
                np.asarray(kw["k_new"][b], np.float32),
            )
