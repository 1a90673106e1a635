"""A sparse-expert family that only draws weights, laid out as a program
serves them packed: optional leading dense layers outside the scan, then
layers of routed experts scanned as one pattern position.

It has what ``bench/weights.py`` reads of a family (``layer_kinds``,
``layer_tensors``, ``top_tensors``, ``program_leaf``) and the program's
tree of shapes (``program_shapes``), and nothing else: no program
configuration, reference layer or work counts.  The seeding tests draw it
at tiny widths and lower its generator at published ones.

``arch`` keys, named as in DeepSeek-V3's ``config.json``: ``hidden_size``,
``num_hidden_layers``, ``first_k_dense_replace`` (leading dense layers),
``intermediate_size`` (their width), ``n_routed_experts``,
``moe_intermediate_size`` (an expert's width) and ``vocab_size`` (0: no
embedding).
"""
import jax
import jax.numpy as jnp

from bench.weights import spread_bf16, steps_bf16, tensor_key, ternary


def layer_kinds(arch):
    first = arch["first_k_dense_replace"]
    return ["dense"] * first + ["moe"] * (arch["num_hidden_layers"] - first)


def linear_shapes(arch, kind):
    d = arch["hidden_size"]
    if kind == "dense":
        f, lead = arch["intermediate_size"], ()
    else:
        f, lead = arch["moe_intermediate_size"], (arch["n_routed_experts"],)
    return {"up": lead + (d, f), "gate": lead + (d, f), "down": lead + (f, d)}


def layer_tensors(key, layer, arch, form, kind):
    if form != "packed":
        raise ValueError(f"form {form!r}: this family is drawn packed only")
    d = arch["hidden_size"]
    out = {name: ternary(tensor_key(key, layer, name), shape)
           for name, shape in linear_shapes(arch, kind).items()}
    out["norm"] = 1 + steps_bf16(tensor_key(key, layer, "norm"), (d,), 8,
                                 1 / 64)
    if kind == "moe":
        out["router"] = spread_bf16(tensor_key(key, layer, "router"),
                                    (d, arch["n_routed_experts"]), d ** -0.5)
    return out


def top_tensors(key, arch, form):
    if not arch["vocab_size"]:
        return {}
    return {"embed": spread_bf16(tensor_key(key, -1, "embed"),
                                 (arch["vocab_size"], arch["hidden_size"]),
                                 0.02)}


def _leaves(arch):
    """``{path: (ShapeDtypeStruct, (layers, tensor name))}``."""
    d, first = arch["hidden_size"], arch["first_k_dense_replace"]
    sds = jax.ShapeDtypeStruct
    out = {}
    if arch["vocab_size"]:
        out["embed/table"] = (sds((arch["vocab_size"], d), jnp.bfloat16),
                              (None, "embed"))
    for i in range(first):
        out[f"dense{i}/norm/scale"] = sds((d,), jnp.float32), (i, "norm")
        for name, (k, n) in linear_shapes(arch, "dense").items():
            out[f"dense{i}/mlp/{name}/w_packed"] = (
                sds((k // 4, n), jnp.uint8), (i, name))
            out[f"dense{i}/mlp/{name}/delta"] = sds((), jnp.float32), (i, name)
    moe = range(first, arch["num_hidden_layers"])
    n_moe, e = len(moe), arch["n_routed_experts"]
    out["stack/pos0/norm/scale"] = sds((n_moe, d), jnp.float32), (moe, "norm")
    out["stack/pos0/router/w"] = (sds((n_moe, d, e), jnp.float32),
                                  (moe, "router"))
    for name, (_, k, n) in linear_shapes(arch, "moe").items():
        out[f"stack/pos0/experts/{name}/w_packed"] = (
            sds((n_moe, e, k // 4, n), jnp.uint8), (moe, name))
        out[f"stack/pos0/experts/{name}/delta"] = (
            sds((n_moe, e), jnp.float32), (moe, name))
    return out


def program_leaf(path, arch):
    got = _leaves(arch).get(path)
    return None if got is None else got[1]


def program_shapes(arch):
    """The program's tree of ``ShapeDtypeStruct``, as ``jax.eval_shape`` of
    its init would give it."""
    tree = {}
    for path, (shape, _) in _leaves(arch).items():
        *head, last = path.split("/")
        node = tree
        for part in head:
            node = node.setdefault(part, {})
        node[last] = shape
    return tree
