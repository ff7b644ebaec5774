"""The plain reference of the token-exact serving tests: a request's greedy
continuation by full recompute. The whole sequence so far goes through the
model's forward, the argmax of its last row is the next token, again.
``model.generate`` is held to the same thing in tests/test_decode.py and
tests/test_llama_decode.py; this one shares no cache, page or step with the
servers it judges.

It costs one compiled program a model whatever the prompts' lengths: the
sequence is padded on the right to the model's positions (a causal model reads nothing
to the right of a row). ``generate`` run op by op is 50 to 70 programs for
every new pair of prompt length and budget, some 4 s each time, and was
most of these files' seconds.
"""
import jax
import numpy as np

import paddle_tpu as paddle

_FORWARDS = {}      # id(model) -> (model, its compiled forward)


def _forward(model):
    """``model``'s logits as one compiled program of (its parameters and
    buffers, the ids): the weights are arguments, so that a model
    quantized or calibrated in place after its first reference is read as
    it stands."""
    held = _FORWARDS.get(id(model))
    if held is None:
        state = list(model.parameters()) + list(model.buffers())

        def logits_of(arrays, ids):
            saved = [t._data for t in state]
            try:
                for t, a in zip(state, arrays):
                    t._data = a
                with paddle.no_grad():
                    return model(paddle.Tensor(ids))._data
            finally:
                for t, d in zip(state, saved):
                    t._data = d
        compiled = jax.jit(logits_of)
        held = _FORWARDS[id(model)] = (
            model, lambda ids: compiled([t._data for t in state], ids))
    return held[1]


def greedy_ref(model, prompt, n, eos_id=None):
    """``prompt`` and its ``n`` greedy tokens (int64, one row), ended after
    ``eos_id`` where that is given and drawn."""
    forward = _forward(model)
    width = model.config.max_position_embeddings
    seq = [int(t) for t in np.asarray(prompt).reshape(-1)]
    assert len(seq) + n <= width, (len(seq), n, width)
    for _ in range(n):
        ids = np.zeros((1, width), np.int64)
        ids[0, :len(seq)] = seq
        row = np.asarray(forward(ids))[0, len(seq) - 1]
        seq.append(int(row.argmax(-1)))
        if seq[-1] == eos_id:
            break
    return np.asarray(seq, np.int64)
