"""The one-shot greedy rollout the engine's tests compare a served
request with: the trajectory the server produced before the engine
existed (``generation.generate_tokens``)."""

import jax.numpy as jnp
import numpy as np

from megatron_llm_tpu.generation import generate_tokens

WINDOW = 64     # the longest sequence these files' engines serve


def reference(cfg, params, prompt, max_new, window=WINDOW):
    """``prompt``'s greedy continuation by ``max_new`` tokens, as a list
    with the prompt in front.  Rolled out to ``window`` positions and cut
    to the request's length (a token depends on none behind it), so that
    the requests of a file run ONE executable: a rollout as long as its
    request was a compile a length, a third of ``test_engine.py``'s time."""
    total = len(prompt) + max_new
    toks = np.zeros((1, max(window, total)), np.int32)
    toks[0, :len(prompt)] = prompt
    out = generate_tokens(cfg, params, jnp.asarray(toks),
                          jnp.asarray([len(prompt)], jnp.int32),
                          eos_id=-1, use_eos_stop=False)
    return np.asarray(out.tokens)[0, :total].tolist()
