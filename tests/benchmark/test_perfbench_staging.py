"""The reader of where a decode loop keeps its caches
(benchmark/harness/staging_readers.py) on a hand-written program, and what it
does with a program that has nothing to read (the parent of PR 40, a cell
without a rewrite stage, a loop that writes no cache row)."""

import types

import pytest
from _util import BENCH  # noqa: F401  (repo root on sys.path)

from benchmark.harness import staging_readers as S

# A loop's body round one cache of 48 rows: the cache comes into VMEM whole,
# has its row written there and goes back.
STAGED = '''
%body (carry: (s32[], bf16[48,512])) -> (s32[], bf16[48,512]) {
  %carry = (s32[]{:T(128)}, bf16[48,512]{1,0:T(8,128)(2,1)}) parameter(0)
  %i = s32[]{:T(128)} get-tuple-element(%carry), index=0
  %c = bf16[48,512]{1,0:T(8,128)(2,1)} get-tuple-element(%carry), index=1
  %copy-start.1 = (bf16[48,512]{1,0:T(8,128)(2,1)S(1)}, bf16[48,512]{1,0:T(8,128)(2,1)}, u32[]{:S(2)}) copy-start(%c)
  %copy-done.1 = bf16[48,512]{1,0:T(8,128)(2,1)S(1)} copy-done(%copy-start.1)
  %dynamic_update_slice.1 = bf16[48,512]{1,0:T(8,128)(2,1)S(1)} dynamic-update-slice(%copy-done.1, %row, %i, %zero)
  %copy-start.2 = (bf16[48,512]{1,0:T(8,128)(2,1)}, bf16[48,512]{1,0:T(8,128)(2,1)S(1)}, u32[]{:S(2)}) copy-start(%dynamic_update_slice.1)
  %copy-done.2 = bf16[48,512]{1,0:T(8,128)(2,1)} copy-done(%copy-start.2)
  ROOT %tuple.1 = (s32[]{:T(128)}, bf16[48,512]{1,0:T(8,128)(2,1)}) tuple(%i, %copy-done.2)
}

ENTRY %main (x: s32[]) -> s32[] {
  %while.1 = (s32[], bf16[48,512]{1,0}) while(%init), condition=%cond, body=%body
}
'''


def ctx(text, **lengths):
    spec = types.SimpleNamespace(**lengths)
    rewriter = types.SimpleNamespace(spec=spec,
                                     decode_program_text=lambda: text)
    family = types.SimpleNamespace(rewriter=rewriter)
    return {"bench": types.SimpleNamespace(family=family)}


@pytest.mark.parametrize("text, new_tokens, expected", [
    (STAGED, 16, 2 * 48 * 512 * 2 / 1e6),  # the cache once in, once out
    (STAGED.replace("S(1)", ""), 16, 0.0),  # the row written in place
    (STAGED, 8, None),  # no cache of the stage's 40 rows: another state
    (None, 16, None),  # a rewriter that keeps no program text
])
def test_staged_mb_of_a_served_decode_program(text, new_tokens, expected):
    got = S.cache_staged_mb_per_token(ctx(
        text, instruction_tokens=24, user_tokens=8, new_tokens=new_tokens))
    assert got == expected


def test_a_cell_without_a_rewrite_stage_has_nothing_to_read():
    family = types.SimpleNamespace()
    assert S.cache_staged_mb_per_token(
        {"bench": types.SimpleNamespace(family=family)}) is None
