"""The dense stack's whole-prompt program works on its group's real rows
(models/decoder.py ``_packed_prompt_pass``): the rows packed end to end
against the same pass over the whole bucket, through the engine both
ways, and what engages it."""

import pytest

from tests import prompt_row_blocks as row_blocks


@pytest.mark.parametrize("group", list(row_blocks.GROUPS))
def test_a_packed_pass_gives_every_real_row_and_page(group):
    """Four blocks of 8 rows a sequence: every real row's logits and
    every real token's K and V of the packed pass are the whole
    bucket's."""
    row_blocks.check_packed_pass("tiny-dense", row_blocks.GROUPS[group])


@pytest.mark.parametrize("group, tp", [(1, 1), (4, 1), (4, 2)])
def test_greedy_tokens_are_the_whole_buckets(monkeypatch, group, tp):
    """A prompt a program, and three prompts run as one program of four
    rows (on one chip, and with the weights sharded over two): the same
    tokens, and the counter says which program ran."""
    row_blocks.check_greedy_identity(
        monkeypatch, "tiny-dense", group=group, tp=tp)


@pytest.mark.parametrize("small, large", [
    ((1, 1024), (1, 2048)),  # one prompt: by its bucket
    ((2, 512), (2, 1024)),  # a group: by its rows in all
    ((1, 1024), (8, 256)),
])
def test_a_program_under_two_row_blocks_holds_no_loop(small, large):
    """What engages the packed pass is the traced shape alone, group x
    bucket: under two blocks of rows the program is, to the letter, the
    jaxpr it is with the loop off (the parent's text: the pass over
    ``[B, S]`` is the code it was); from two blocks on it holds the
    counted loops.  A decode step never changes."""
    row_blocks.check_small_programs_hold_no_loop("tiny-dense", small, large)
