"""The fused kernels carry their own names into the compiled program
(`pl.pallas_call(name=...)`), and the trace reduction's signature rule
(`trace.kernel_kind`) gives each the same name. The instructions are as a
compile for a TPU v5e prints them (`tests/test_tpu_compile.py` compiles
the kernels), tiled layouts and all."""
import pytest

import bench_cases  # noqa: F401  (puts the repository on the path)
from bench import trace as T

COMPILED = [
    '%fused_fwd.1 = (bf16[2,1,256,128]{3,2,1,0:T(8,128)(2,1)}, '
    'f32[2,1,256,1]{3,2,1,0:T(8,128)S(1)}) custom-call(%copy-done, '
    '%copy-done.1, %broadcast.1, %a_0_.1, %a_1_.1, /*index=5*/%a_2_.1), '
    'custom_call_target="tpu_custom_call"',
    'ROOT %fused_dq.1 = f32[2,1,256,128]{3,2,1,0:T(8,128)} custom-call('
    '%copy-done.5, %copy-done.6, %broadcast.1, %copy-done.1, %copy-done.2, '
    '/*index=5*/%copy-done.3, %copy-done.4, %copy, %copy.1), '
    'custom_call_target="tpu_custom_call"',
    'ROOT %fused_dkv.1 = (f32[2,256,128]{2,1,0:T(8,128)}, '
    'f32[2,256,128]{2,1,0:T(8,128)}) custom-call(%copy-done.5, '
    '%copy-done.6, %broadcast.1, %copy-done.1, %copy-done.2, '
    '/*index=5*/%copy-done.3, %copy-done.4, %copy, %copy.1), '
    'custom_call_target="tpu_custom_call"',
]


@pytest.mark.parametrize("text", COMPILED, ids=["fwd", "dq", "dkv"])
def test_kernel_kind_is_the_kernels_own_name(text):
    own = text.removeprefix("ROOT ").split(" ", 1)[0].lstrip("%")
    assert T.kernel_kind(text) == own.split(".", 1)[0]
    assert T.short_op(text.removeprefix("ROOT ")) == \
        f"{own.split('.', 1)[0]}:{own}"
