"""A causal language model as the rewrite stage takes it: a value.

`pipelines.PromptRewriter` names no model.  It is handed a configuration
whose ``language_model()`` returns this record, and calls nothing else of
the model's module:

    prefill(params, config, ids [T], max_len=)
        -> (float32 logits after the last id, the decode state, the
            counters so far [len(counters)] int32, what the model records
            of the prompt beside ids and logits - () where nothing; else a
            tree of arrays [..., T, ...] with the T positions on
            `POSITION_AXIS`, in the prompt's order)
    decode(params, config, logits, state, counters, position=, new_tokens=)
        -> (new ids [new_tokens] int32, the float32 logits each was chosen
            from [new_tokens, ...], what it records of the decoded ids, the
            state, the counters) - all ``new_tokens`` greedy steps in one
            loop on the device.  ``logits`` is what ``prefill`` returned:
            "what follows the prompt" only for a model that predicts the
            NEXT position - one whose logits at a position predict that
            position (it decodes by unmasking) ignores them.  A trip of the
            loop need not yield one id: a model that decodes a block of
            positions together takes ``new_tokens`` in multiples of its
            ``decode_multiple``, and records (third result) what of a
            block's passes the ids and logits alone do not say

and, where the model can take a prompt's suffix into the state its prefix
left (``prefill_from`` is None where it cannot):

    prefill_from(params, config, ids [T], max_len=, state=, counters=,
                 position=)
        -> what ``prefill`` returns, for ids at ``position`` onward
            (static, like T a multiple of ``prompt_multiple``) through
            ``state`` and ``counters`` as ``prefill`` returned them for the
            ``position`` ids before.  The state handed in is read, not
            consumed - the one returned is new, so one prefix serves many
            suffixes - and the result is the prefill of all the ids, but
            for the record (fourth result), which is of the T entering ids:
            whoever kept the record of the ``position`` ids before puts the
            two together along `POSITION_AXIS`
            (`pipelines.PromptRewriter` does, in the request's own program).
"""

from __future__ import annotations

from typing import Any, Callable, NamedTuple, Optional, Tuple


# where a prompt's positions lie in every array of what ``prefill`` records
POSITION_AXIS = 1


class LanguageModel(NamedTuple):
    config: Any
    prefill: Callable
    decode: Callable
    counters: Tuple[str, ...]  # names of the counters the programs carry
    prompt_multiple: int  # a prompt's length is a multiple of this
    vocab_size: int  # ids are 0 .. vocab_size - 1
    # a byte-level model reads text as its UTF-8 bytes, byte b the id
    # b + byte_offset (the ids below are special and never fed); None: the
    # model has a vocabulary of words
    byte_offset: Optional[int] = None
    prefill_from: Optional[Callable] = None
    # ``new_tokens`` is a multiple of this: the ids a trip of the decode
    # loop yields (a block, for a model that decodes by blocks)
    decode_multiple: int = 1
