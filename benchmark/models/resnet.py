"""The program under test for ``"model": "resnet"`` configurations: the
repo's ``models.resnet.ResNet`` trained by ``DataParallelStrategy`` with
``optax.sgd`` and momentum, as a user's ``map_fun`` would build it.

The weights are the benchmark's (``reference/<reference>.make_variables``,
from the seed, made on the mesh in one jitted call by
``strategy.init_state``); the model, the strategy, the compiled step and
the placement are the program's.
"""

from __future__ import annotations

from benchmark import child, harness


class TrainProgram:
    """The compiled step with its state: ONE object, driven through its
    first steps in set-up and then handed to the window."""

    def __init__(self, cfg: dict, seed: int, devices):
        import jax
        import jax.numpy as jnp
        import optax

        from tensorflowonspark_tpu.models.resnet import ResNet
        from tensorflowonspark_tpu.parallel import sharding as sh
        from tensorflowonspark_tpu.parallel.strategy import \
            DataParallelStrategy

        ref = harness.load_module("reference", cfg["reference"])
        self.ref = ref
        self.strategy = DataParallelStrategy(devices=list(devices))
        model = ResNet(stage_sizes=tuple(cfg["stage_sizes"]),
                       num_filters=cfg["num_filters"],
                       num_classes=cfg["num_classes"],
                       dtype=jnp.dtype(cfg["dtype"]))
        tx = optax.sgd(cfg["learning_rate"], momentum=cfg["momentum"])
        # the seed goes in as an argument: a seed baked into the program
        # would make every seed a program of its own, compiled anew
        key = child.seed_key(seed)
        self.state = self.strategy.init_state(
            lambda key: ref.make_variables(key, cfg)["params"], tx, key)
        self.state.extras["batch_stats"] = jax.jit(
            lambda key: ref.make_variables(key, cfg)["batch_stats"],
            out_shardings=sh.replicated(self.strategy.mesh))(key)

        def loss_fn(params, batch, extras):
            x, y = batch
            x = x.astype(model.dtype) / 127.5 - 1.0          # uint8 -> [-1, 1]
            logits, updates = model.apply(
                {"params": params, "batch_stats": extras["batch_stats"]}, x,
                train=True, mutable=["batch_stats"])
            loss = optax.softmax_cross_entropy_with_integer_labels(
                logits, y).mean()
            return loss, {"extras": {"batch_stats": updates["batch_stats"]}}
        loss_fn.has_aux = True
        self._step = self.strategy.build_train_step(loss_fn)
        # the probes read the optimizer's own state: the first gradient AS
        # THE OPTIMIZER GOT IT is the momentum trace after one step
        self._grad_norms = jax.jit(
            lambda opt_state: ref.leaf_norms(opt_state[0].trace))
        self._delta_norms = jax.jit(lambda a, b: ref.leaf_norms(
            jax.tree.map(jnp.subtract, a, b)))
        self._copy = jax.jit(lambda t: jax.tree.map(jnp.copy, t))

    def shard(self, arrays):
        return self.strategy.shard_batch(arrays)

    def step(self, batch):
        """One training step through the program's compiled step; returns
        the loss, still on the device."""
        self.state, metrics = self._step(self.state, batch)
        return metrics["loss"]

    def params_copy(self):
        return self._copy(self.state.params)

    def grad_norms(self):
        return self._grad_norms(self.state.opt_state)

    def delta_norms(self, params0):
        return self._delta_norms(self.state.params, params0)

    def param_shard_devices(self) -> int:
        """Fewest distinct devices any parameter has shards on."""
        import jax

        return min(len({s.device for s in leaf.addressable_shards})
                   for leaf in jax.tree.leaves(self.state.params))

    def ready(self):
        import jax

        jax.block_until_ready(self.state)

    def free(self):
        """Drop the state and the compiled programs, so that the float32
        reference has the chip's memory to itself."""
        import jax

        self.state = self._step = None
        jax.clear_caches()


def build_train(cfg: dict, seed: int, devices) -> TrainProgram:
    return TrainProgram(cfg, seed, devices)
