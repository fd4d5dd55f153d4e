"""Model families, one module each, chosen by a configuration file's
``reference`` key as its plain reference is (``bench/reference/<name>``).

A family module gives:

    model_config(m)          the program's ModelConfig for config ``m``
    LEAVES, shapes(m)        its seeded weights: leaf ids (part of each
                             leaf's key) and (shape, a_exp) of each leaf
    params(mkey, lo, hi)     the program's parameter tree from those
                             weights, made on the device in one jitted
                             call (``mkey`` is ``config_key(m)``)
    moe_dims(m)              (d_model, d_expert, experts, top_k, layers)
    token_flops, prefill_flops   model FLOPs of a token and of a prompt

So a new model family is a new file here and one in ``bench/reference/``;
the harness, the weights and the readers are not edited.
"""
from __future__ import annotations

import importlib


def family(m: dict):
    return importlib.import_module(f"bench.families.{m['reference']}")


def config_key(m: dict):
    """The scalar top-level entries of ``m``, hashable (a jit's static
    argument)."""
    return tuple(sorted((k, v) for k, v in m.items()
                        if isinstance(v, (int, float, str)) or v is None))
