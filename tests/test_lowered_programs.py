"""`tools/lowered_programs.py`: the count of grouped products a lowered
program holds (ISSUE 36). A kernel stands once in the jitted function that
holds it, so an occurrence counts as often as its function is called."""

import importlib.util
import os

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
spec = importlib.util.spec_from_file_location(
    "lowered_programs", os.path.join(ROOT, "tools", "lowered_programs.py"))
tool = importlib.util.module_from_spec(spec)
spec.loader.exec_module(tool)

KERNEL = ('    %1 = stablehlo.custom_call @tpu_custom_call(%arg0) '
          '{kernel_name = "apex_gmm"} : (tensor<4xf32>) -> tensor<4xf32>\n')
RAGGED = ('    %2 = "chlo.ragged_dot"(%arg0, %arg1, %arg2) '
          '{ragged_dot_dimension_numbers = #chlo.ragged_dot<lhs = [1]>} : '
          '(tensor<4xf32>) -> tensor<4xf32>\n')


def module(main, **private):
    text = "module @jit_step {\n  func.func public @main(%arg0: f32) {\n"
    text += main + "  }\n"
    for name, body in private.items():
        text += f"  func.func private @{name}(%arg0: f32) {{\n{body}  }}\n"
    return text + "}\n"


CALL = "    %0 = call @{}(%arg0) : (f32) -> f32\n"
CASES = {
    "a_dense_program": (module(CALL.format("_where"), _where=""), 0, 0),
    "the_parents_step": (module(RAGGED * 3), 0, 3),
    "kernels_in_main": (module(KERNEL * 3), 3, 0),
    "one_jitted_call_three_times": (
        module(CALL.format("_call") * 3, _call=KERNEL), 3, 0),
    "two_shapes_in_four_scan_bodies": (
        module((CALL.format("_call") * 2 + CALL.format("_call_7")) * 4,
               _call=KERNEL, _call_7=KERNEL), 12, 0),
    "through_a_function_called_twice": (
        module(CALL.format("moe_ffn") * 2,
               moe_ffn=CALL.format("_call") * 3, _call=KERNEL), 6, 0),
    "a_function_nothing_calls": (module("", _call=KERNEL), 0, 0),
}


@pytest.mark.parametrize("name", sorted(CASES))
def test_grouped_products_are_counted_as_often_as_they_run(name):
    text, kernels, ragged = CASES[name]
    assert tool.grouped_products(text) == {"apex_gmm": kernels,
                                           "ragged_dot": ragged}
