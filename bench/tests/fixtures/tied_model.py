"""A model module with a tied output head: ``reference.py``'s own model
without ``lm_head``, the logits taken against the embedding's first
``vocab`` rows, as the program's ``tie_embeddings`` path takes them."""
import reference

sizes = reference.sizes
program_fields = reference.program_fields


def init_params(key, cfg):
    params = reference.init_params(key, cfg)
    del params["lm_head"]
    return params


def loss(params, tokens, labels, z, prec="f32"):
    x, auxs = reference.hidden_states(params, tokens, z, prec)
    head = params["embed"][:z["vocab"]].T
    return (reference.head_nll(x, head, labels, prec)
            + z["aux_coef"] * auxs.sum())
