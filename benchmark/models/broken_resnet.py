"""A control of the check, never of a cell: the ``resnet`` program with the
timed path broken underneath.  Its step computes the loss and returns its
state unchanged.  ``tests/benchmark`` drives a whole run over it and sees
``correct`` come out false."""

from benchmark.models import resnet


class FrozenProgram(resnet.TrainProgram):
    def step(self, batch):
        kept = self._copy(self.state)
        _, metrics = self._step(self.state, batch)
        self.state = kept
        return metrics["loss"]


def build_train(cfg: dict, seed: int, devices):
    return FrozenProgram(cfg, seed, devices)
