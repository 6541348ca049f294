"""What the family builders share: seeded keys, on-device weight
initialisation from the seed, and FLOP arithmetic on shapes."""

import functools

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec


def seed_key(seed: int, stream: int):
    """A PRNG key from any non-negative seed (the driver's exceed 2**31) and
    a stream number (one per weight tree)."""
    seed = int(seed)
    key = jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF), seed >> 31)
    return jax.random.fold_in(key, stream)


def init_on_device(init_fn, key, dtype, mesh, table_std=None):
    """A parameter tree with the structure and shapes of `init_fn(key)`
    (read abstractly: the program's initialiser is never run), filled on the
    mesh, replicated, in the served dtype, from `key`:

      * `scale` leaves and `*_norm` leaves are ones, `bias` leaves zeros;
      * leaves whose path ends with a key of `table_std` ("shared",
        "attn/q/kernel") are N(0, std^2): embedding tables, and kernels the
        published initialiser scales otherwise;
      * every other leaf is a kernel [..., in, out] (HWIO for convolutions),
        N(0, 1/fan_in).

    One small jitted generator per distinct (shape, std) - some tens for a
    model of 1700 leaves - called once per leaf with that leaf's key.  ONE
    jitted call that returns the whole tree was tried first and compiled for
    673 s on the chip machine (my chip run, PR 23): the cost is per output,
    not per random number.  Each generator is in the compile cache after a
    cell's first run, and nothing is made on the host."""
    import math

    import numpy as np

    table_std = table_std or {}
    replicated = NamedSharding(mesh, PartitionSpec())
    abstract = jax.eval_shape(init_fn, key)
    leaves, treedef = jax.tree_util.tree_flatten_with_path(abstract)
    keys = jax.device_put(jax.random.split(key, len(leaves)), replicated)

    @functools.lru_cache(maxsize=None)
    def generator(shape, std):
        return jax.jit(
            lambda ks, i: (jax.random.normal(ks[i], shape, jnp.float32)
                           * std).astype(dtype), out_shardings=replicated)

    @functools.lru_cache(maxsize=None)
    def constant(shape, value):
        return jax.jit(lambda: jnp.full(shape, value, dtype),
                       out_shardings=replicated)

    out = []
    for i, (path, leaf) in enumerate(leaves):
        keys_ = [str(getattr(k, "key", getattr(k, "idx", ""))) for k in path]
        name, where = keys_[-1], "/".join(keys_)
        shape = tuple(leaf.shape)
        if name == "scale" or name.endswith("_norm"):
            out.append(constant(shape, 1.0)())
        elif name == "bias":
            out.append(constant(shape, 0.0)())
        else:
            special = [v for k, v in table_std.items()
                       if where == k or where.endswith("/" + k)]
            if special:
                std = float(special[0])
            else:
                fan_in = (int(np.prod(shape[:-1])) if len(shape) == 4
                          and "kernel" == name and shape[0] == shape[1]
                          else shape[-2])
                std = 1.0 / math.sqrt(fan_in)
            out.append(generator(shape, std)(keys, i))
    return jax.tree_util.tree_unflatten(treedef, out)


def scheduler_kwargs(config: dict) -> dict:
    """The configuration's beta schedule as `get_scheduler` keyword arguments."""
    return {k: config["scheduler"][k] for k in (
        "num_train_timesteps", "beta_start", "beta_end", "beta_schedule",
        "steps_offset")}


def tree_nbytes(tree) -> int:
    return sum(int(x.size) * x.dtype.itemsize for x in jax.tree.leaves(tree))


def attention_cost(batch, lq, lk, heads, head_dim, itemsize=2):
    """(FLOPs, bytes) of softmax(q k^T) v for one call: the two matmuls, and
    q, k, v read once and the output written once."""
    flops = 4 * batch * heads * lq * lk * head_dim
    nbytes = itemsize * batch * heads * head_dim * (2 * lq + 2 * lk)
    return flops, nbytes
