"""Ahead-of-time compiles for the v5e, with no chip present: what the
``tests/test_tpu_aot*.py`` files share.

libtpu ships next to jax in this installation, and
``jax.experimental.topologies.get_topology_desc("v5e:2x2", "tpu")``
describes four ``TPU v5 lite`` devices without one being attached.
Abstract arguments placed on such a device run the real XLA:TPU and
Mosaic compilers through ``jitted.lower(...).compile()`` — so a kernel
Mosaic refuses, or a step program whose temporaries outgrow the chip, is
found here on the CPU instead of on chip time.  What these tests assert
was confirmed on the chip by ``chip_smoke.py`` (PERF.md "Bring-up").

The files are apart by what they compile, none past 300 s alone
(``tests/conftest.py LONGEST_FIRST``): ``test_tpu_aot.py`` the kernels
and the dense, latent, pattern and window stacks' step programs,
``test_tpu_aot_long_prompts.py`` the three long cells' prompt programs
(one compile each, a module fixture) and the GLM cut's selection,
``test_tpu_aot_states.py`` the EvaByte, Granite and Keye cuts,
``test_tpu_aot_head_64.py`` the packed head of 64 and the LFM2 cut.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from vgate_tpu.models.specs import spec_for_model_id

PAGE = 32


@pytest.fixture(scope="module")
def v5e():
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc("v5e:2x2", "tpu")
    except Exception as exc:  # noqa: BLE001 — any failure means "no libtpu"
        pytest.skip(f"no TPU AOT topology in this installation: {exc!r}")
    return SingleDeviceSharding(topo.devices[0])


def abstract_on(sharding):
    return lambda shape, dtype: jax.ShapeDtypeStruct(
        shape, dtype, sharding=sharding
    )


class MosaicRefusal(Exception):
    """The compile failed inside Mosaic with the message on record."""


def compile_expecting(fragment, compile_kernel, *args, **kwargs):
    """Only the recorded Mosaic message counts as the expected failure;
    any other error (an API change, a wrong shape here) stays an error."""
    try:
        compile_kernel(*args, **kwargs)
    except Exception as exc:  # noqa: BLE001 — re-raised below
        if type(exc).__name__ == "MosaicError" and fragment in str(exc):
            raise MosaicRefusal(str(exc)) from exc
        raise


def operations(compiled):
    """The compiled program's text without the tables of source files
    and frames under its first line: they name every file a traced
    function came through, a test file that ran before on this process
    among them (``tests/test_greedy_head.py`` under a name a test looks
    for)."""
    text = compiled.as_text()
    start = text.find("\nFileNames\n")
    if start < 0:
        return text
    frames = text.index("\nStackFrames\n", start)
    return text[:start] + text[text.index("\n\n", frames):]


def assert_no_logits_array(text, rows, vocab):
    """The fused head's program: no float32 logits as a buffer, in the
    head's layout or flat, and the pass itself in it."""
    assert f"f32[{rows},{vocab}]" not in text
    assert f"f32[{rows * vocab}]" not in text
    assert "greedy_head" in text


# the benchmark's cuts of two presets (perfbench/configs): what the
# cells serve
MISTRAL_CUT = ("mistralai/Mistral-Small-4-119B-2603", dict(
    name="mistral-cut", num_layers=4, num_experts=32, vocab_size=32768,
    eos_token_id=32767, bos_token_id=32766, extra_stop_ids=()))
EXAONE_CUT = ("LGAI-EXAONE/K-EXAONE-236B-A23B", dict(
    name="exaone-cut", num_layers=5, num_experts=16, vocab_size=19200,
    eos_token_id=19199, bos_token_id=19198))
MELLUM_CUT = ("JetBrains/Mellum2-12B-A2.5B-Instruct", dict(
    name="mellum-cut", num_layers=8))


def cut_and_shapes(A, preset, changes):
    """(spec, abstract bf16 parameters) of a preset cut to a cell's."""
    import dataclasses

    from vgate_tpu.models.decoder import init_params

    spec = dataclasses.replace(spec_for_model_id(preset), **changes)
    params = jax.tree.map(
        lambda x: A(x.shape, x.dtype),
        jax.eval_shape(
            lambda: init_params(spec, jax.random.PRNGKey(0), jnp.bfloat16)))
    return spec, params


def prompt_program(A, spec, params, pool, v_pool, state, bucket=8192,
                    B=1):
    """The cell's prompt program: ``B`` prompts (ONE, in the long cells)
    in ``bucket``."""
    from vgate_tpu.runtime.step_programs import _prefill_step

    return _prefill_step.lower(
        params, spec, A((B, bucket), jnp.int32), A((B,), jnp.int32),
        pool, v_pool, A((B, bucket // PAGE), jnp.int32),
        A((B,), jnp.float32), A((B,), jnp.float32), A((B,), jnp.int32),
        A((2,), jnp.uint32), use_pallas=True,
        seeds=A((B,), jnp.int32), steps=A((B,), jnp.int32),
        **({} if state is None else {"state": state,
                                     "slots": A((B,), jnp.int32)}),
    ).compile()


def nbytes(tree):
    return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(tree))


def assert_no_buffer(text, rows, width, dtypes=("f32", "bf16", "s32")):
    """No array of ``rows x width`` in the compiled program, whatever
    its type: a temporary that goes by ALL (row, choice) pairs."""
    for dtype in dtypes:
        shape = f"{dtype}[{rows},{width}]"
        found = [line for line in text.splitlines() if shape in line]
        assert not found, found[0][:300]


def assert_no_copy_of(text, *shapes, dtype="bf16"):
    """No operation of the compiled program MAKES an array of one of
    ``shapes``: a period's (or a unit's repeats') matrices sliced out of
    the stacked parameters into a buffer of their own (a fusion of a
    dynamic slice, a ``copy-done``), which the products would then read
    in place of the parameter (PR 48).  A parameter of that shape, a
    tuple's element and a bitcast move nothing."""
    views = (" parameter(", " get-tuple-element(", " bitcast(")
    for shape in shapes:
        made = f" = {dtype}[{','.join(map(str, shape))}]"
        found = [line for line in text.splitlines()
                 if made in line and not any(v in line for v in views)]
        assert not found, found[0][:300]


# temporary bytes of the parent's (PR 53, commit 1dddb33) prompt programs
# by the same compile: the bias of a selection's tile is VMEM scratch of
# the launch, no array of the program's
PARENT_53_TEMP_BYTES = {"glm": 2_439_488_512, "mistral": 814_459_904,
                        "keye": 822_795_776}


def counted_loops(text, scope):
    """The ``while`` of the compiled program traced under ``scope``
    whose condition holds no constant: its trips are an operand."""
    import re

    found = 0
    for line in text.splitlines():
        at = re.search(r" while\(.*condition=%([\w.\-]+)", line)
        if not at or f'{scope}/while"' not in line:
            continue
        start = text.index(f"\n%{at.group(1)} (")
        body = text[start:text.index("\n}", start)]
        found += "compare(" in body and " constant(" not in body
    return found
