"""scripts/step_hlo.py: what it strips from a compiled module, so that two
checkouts that compile the same step write the same file and a changed
program, or an operation moved to another scope, does not; and that it
compiles every configuration the benchmark names."""

import os
import sys

import jax
import jax.numpy as jnp
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "scripts"))
import step_hlo  # noqa: E402


def _one_program_at_two_places():
    """One program, one name, traced from two places in the source."""
    def step(x, y):
        return jnp.sin(x) * 2.0 + y
    first = step

    def step(x, y):  # noqa: F811
        return jnp.sin(x) * 2.0 + y
    return first, step


def _compiled(f, dtype=jnp.float32):
    """The compiled module of ``f`` on two ``[8, 128]`` arrays."""
    x = jax.ShapeDtypeStruct((8, 128), dtype)
    return jax.jit(f).lower(x, x).compile().as_text()


def test_the_source_of_an_operation_is_stripped():
    a, b = map(_compiled, _one_program_at_two_places())
    assert a != b                   # the metadata names other lines
    got = step_hlo.stripped(a)
    assert "source_line" not in got and "stack_frame_id" not in got
    assert 'op_name="jit(step)/' in got     # the scope path is kept
    assert got == step_hlo.stripped(b)


def _scoped(x, y):
    with jax.named_scope("fm.gather"):
        return jnp.sin(x) * 2.0 + y


@pytest.mark.parametrize("change", ["dtype", "operation", "scope"])
def test_a_changed_program_still_differs(change):
    step = _one_program_at_two_places()[0]
    base = step_hlo.stripped(_compiled(step))
    changed = {"dtype": lambda: _compiled(step, jnp.bfloat16),
               "operation": lambda: _compiled(
                   lambda x, y: jnp.cos(x) * 2.0 + y),
               # the same operations, read by another per-layer metric
               "scope": lambda: _compiled(_scoped)}[change]()
    assert step_hlo.stripped(changed) != base


def test_names_are_numbered_by_first_appearance():
    text = """HloModule m, entry_computation_layout={(f32[4]{0})->f32[4]{0}}

FileNames
1 "/a/b.py"

StackFrames
1 {file_location_id=1 parent_frame_id=1}

ENTRY %main.7 (param.3: f32[4]) -> f32[4] {
  %param.3 = f32[4]{0} parameter(0), metadata={op_name="jit(f)/{x}" source_line=3}
  %sine.5 = f32[4]{0} sine(%param.3), metadata={op_name="s" source_file="/a/b.py"}
  ROOT %add.9 = f32[4]{0} add(%sine.5, %param.3)
}
"""
    shifted = text.replace("sine.5", "sine.6").replace("add.9", "add.12")
    got = step_hlo.stripped(text)
    assert "FileNames" not in got and "StackFrames" not in got
    assert "source_" not in got and "/a/b.py" not in got
    assert got == step_hlo.stripped(shifted)
    assert '%sine.#2 = f32[4]{0} sine(%param.#1), metadata={op_name="s"}' \
        in got
    assert 'metadata={op_name="jit(f)/{x}"}' in got
    # a name is renamed where it is one, not inside a quoted string, and an
    # opcode that is some instruction's name too is left as it is
    assert "(param.#1: f32[4])" in got
    assert "ROOT %add.#3 = f32[4]{0} add(%sine.#2, %param.#1)\n" in got
    assert "%add.#3 = f32[4]{0} add(" in step_hlo.stripped(
        text.replace("add.9", "add"))


def test_every_configuration_the_benchmark_names_has_its_shapes():
    """A configuration the benchmark adds without its rungs would be left
    out of the proof that every cell's step is unchanged: the script
    refuses to run instead."""
    assert set(step_hlo.configurations()) == set(step_hlo.SHAPES)
    assert step_hlo.configurations()["kdd2012-fm-dp4"] == 4
