"""Minimal data-parallel training loop — the "hello world" of the
framework (reference: examples/simple/distributed/
distributed_data_parallel.py: toy model + apex DDP + amp O1).

Runs anywhere: real TPU chips or virtual CPU devices
(XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu).

    python examples/simple_distributed.py
"""

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from apex_tpu import amp
from apex_tpu.mlp import MLP
from apex_tpu.optimizers import FusedAdam
from apex_tpu.transformer import parallel_state


def main():
    mesh = parallel_state.initialize_model_parallel()
    dp = mesh.shape["dp"]
    print(f"devices: {jax.device_count()}, dp={dp}")

    model = MLP([16, 32, 1], activation="relu")
    mp = amp.initialize(opt_level="O1")  # bf16-compute policy + scaler
    opt = FusedAdam(lr=1e-2)

    params = model.init(jax.random.PRNGKey(0))
    opt_state = opt.init(params)
    amp_state = mp.init()

    def train_step(params, opt_state, amp_state, x, y):
        def loss_fn(p):
            pred = model.apply(mp.policy.cast_to_compute(p), x)
            loss = jnp.mean((pred.astype(jnp.float32) - y) ** 2)
            return mp.scale_loss(amp_state, loss), loss

        grads, loss = jax.grad(loss_fn, has_aux=True)(params)
        grads = jax.tree.map(lambda g: jax.lax.pmean(g, "dp"), grads)
        grads, finite, new_amp = mp.unscale_and_adjust(amp_state, grads)
        new_params, new_opt = opt.step(
            opt_state, grads, params, grads_finite=finite
        )
        return new_params, new_opt, new_amp, jax.lax.pmean(loss, "dp")

    pspec = jax.tree.map(lambda _: P(), params)
    ospec = jax.tree.map(lambda _: P(), opt_state)
    aspec = jax.tree.map(lambda _: P(), amp_state)
    step = jax.jit(
        jax.shard_map(
            train_step, mesh=mesh,
            in_specs=(pspec, ospec, aspec, P("dp"), P("dp")),
            out_specs=(pspec, ospec, aspec, P()),
        )
    )

    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(64 * dp, 16)).astype(np.float32))
    y = jnp.sum(x[:, :4], axis=1, keepdims=True)

    for i in range(200):
        params, opt_state, amp_state, loss = step(
            params, opt_state, amp_state, x, y
        )
        if i % 50 == 0:
            print(f"step {i:4d}  loss {float(loss):.5f}")
    print(f"final loss {float(loss):.5f}")
    assert float(loss) < 0.05, "did not converge"
    print("OK")


if __name__ == "__main__":
    main()
