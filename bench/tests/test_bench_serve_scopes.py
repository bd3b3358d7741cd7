"""The served model's scope reduction: the grouped products, which the TPU
compiler renames ``ragged-dot-none``, count under the scope of the
instruction that uses their result."""
import pytest

from bench import scopes, serve_scopes
from bench.tests.test_bench_scopes import trace
from repro.core import spans

BODY = "jit(serve_decode)/serve.decode/while/body/closed_call"
TEXT = f"""HloModule jit_serve_decode

%body (p: (s32[], bf16[8,4])) -> (s32[], bf16[8,4]) {{
  %ragged-dot-metadata = (s32[3]{{0}}, s32[3]{{0}}) custom-call(%p), metadata={{op_name="ragged-dot-metadata"}}
  %get-tuple-element.1 = s32[3]{{0}} get-tuple-element(%ragged-dot-metadata), index=0
  %ragged-dot-none = bf16[8,4]{{1,0}} custom-call(%get-tuple-element.1, %p), metadata={{op_name="ragged-dot-none"}}
  %fusion.2 = bf16[8,4]{{1,0}} fusion(%ragged-dot-none), kind=kLoop, metadata={{op_name="{BODY}/moe.routed/mul"}}
  %fusion.3 = bf16[8,4]{{1,0}} fusion(%fusion.2), kind=kLoop, metadata={{op_name="{BODY}/mla.attn/dot_general"}}
  %copy.4 = bf16[8,4]{{1,0}} copy(%fusion.3)
}}

ENTRY %main (a: bf16[8,4]) -> bf16[8,4] {{
  %while.5 = (s32[], bf16[8,4]) while(%a), body=%body, metadata={{op_name="jit(serve_decode)/serve.decode/while"}}
  %unused = bf16[8,4]{{1,0}} custom-call(%a), metadata={{op_name="lonely"}}
}}
"""


def paths():
    return {n: p for n, p, _ in scopes.instructions(TEXT)}


def test_a_renamed_instruction_takes_its_users_path():
    got = serve_scopes.user_paths(TEXT, paths())
    assert got["ragged-dot-none"] == f"{BODY}/moe.routed/mul"
    # through a user without a path to the product it feeds
    assert got["ragged-dot-metadata"] == f"{BODY}/moe.routed/mul"
    # paths and empty paths stay as they were; a bare name no instruction
    # with a path uses stays bare
    for name in ("fusion.2", "fusion.3", "copy.4", "while.5", "unused",
                 "get-tuple-element.1"):
        assert got[name] == paths()[name]


def test_the_grouped_products_count_under_the_routed_experts():
    ops = [("while.5", 100, 900), ("ragged-dot-metadata", 150, 200),
           ("ragged-dot-none", 200, 500), ("fusion.2", 500, 600),
           ("fusion.3", 600, 800)]
    pd = trace(ops, [(100, 1000)], module="jit_serve_decode")
    plain = scopes.by_scope(pd, (0, 4000), "jit_serve_decode", paths(), spans)
    fixed = scopes.by_scope(pd, (0, 4000), "jit_serve_decode",
                            serve_scopes.user_paths(TEXT, paths()), spans)
    # by the bare names alone the products fall outside every scope
    assert plain[spans.ROUTED] == pytest.approx(0.1e-3)
    assert plain["unscoped"] == pytest.approx(0.35e-3)
    assert fixed[spans.ROUTED] == pytest.approx(0.45e-3)
    assert fixed[spans.DECODE] == pytest.approx(0.8e-3)
    assert fixed[spans.MLA] == pytest.approx(0.2e-3)
    assert fixed["unscoped"] == 0.0
