"""Build the system under test for one cell through the program's normal
path: ``make_round_engine(model, fl, topology)`` and a
``RoundRunner`` of one round per compiled chunk."""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from benchlib import spec as specs
from benchlib import traffic as traffic_mod
from benchlib import weights

# FLConfig fields the reference implements; every other field keeps the
# program's default (FedAvg, no downlink compression, all clients).
FL_KEYS = {"uplink_compressor", "backend", "error_feedback", "local_steps",
           "local_lr", "qsgd_block", "topk_fraction"}


def round_key(seed: int):
    """The key of the program's own round randomness (``FLState.rng``)."""
    return traffic_mod.seed_key(seed, 4)


@dataclasses.dataclass
class Cell:
    name: str
    config: dict
    traffic: dict
    family: object            # bench/models/<family>.py
    model: object             # repro Model
    fl: object                # repro FLConfig
    engine: object
    runner: object
    data_fn: object
    layout: dict
    devices: list


def build(resolved: dict, devices) -> Cell:
    """The cell's engine and runner; nothing in them depends on the seed,
    which reaches the program at run time (``init_state``)."""
    from repro.configs.registry import get_arch
    from repro.core.engine import RoundRunner, Topology, make_round_engine
    from repro.core.types import FLConfig
    from repro.models.model import Model

    config, traffic = resolved["config"], resolved["traffic"]
    sizes = config["sizes"]
    family = specs.required_module("models", config["family"])
    cfg = dataclasses.replace(get_arch(config["registry"]), **sizes)
    model = Model(cfg)
    unknown = set(traffic["fl"]) - FL_KEYS
    if unknown:
        raise ValueError(f"traffic sets FLConfig fields the reference does "
                         f"not implement: {sorted(unknown)}")
    fl = FLConfig(**traffic["fl"])
    if traffic["topology"] != "sim":
        raise ValueError(f"unknown topology {traffic['topology']!r}")
    engine = make_round_engine(model, fl, Topology.sim(traffic["clients"]))
    data_fn = traffic_mod.make_data_fn(traffic, sizes, family, cfg.dtype)
    runner = RoundRunner(engine, data_fn, chunk=1)
    layout = family.layout(sizes)
    return Cell(name=resolved["name"], config=config, traffic=traffic,
                family=family, model=model, fl=fl, engine=engine,
                runner=runner, data_fn=data_fn, layout=layout,
                devices=list(devices[:resolved["chips"]]))


def init_state(cell: Cell, seed: int):
    """The engine's initial state with the benchmark's weights, its round
    key and its first round from the seed, made on the device in one jitted
    call that takes them as arguments."""
    dtype = cell.model.cfg.dtype
    abstract = cell.model.abstract_params()

    def init(wkey, rng, first):
        st = cell.engine.init_fn(jax.random.PRNGKey(0))
        params = weights.to_tree(weights.make(cell.layout, wkey, dtype),
                                 abstract)
        return dataclasses.replace(st, params=params, rng=rng, round=first)

    args = (weights.weight_key(seed), round_key(seed),
            jnp.int32(traffic_mod.first_round(seed)))
    with jax.default_device(cell.devices[0]):
        return jax.jit(init)(*args)


class ReferenceInputs:
    """``inputs(seed)``: the reference's float32 copies of the weights the
    program starts from, ``batch(k)``, the batch of the run's k-th round,
    and the key of the reference's own randomness; compiled once for every
    seed."""

    def __init__(self, layout: dict, data_fn, dtype):
        self._params = jax.jit(lambda k: {
            p: v.astype(jnp.float32) for p, v in
            weights.make(layout, k, dtype).items()})
        self._data = jax.jit(data_fn)

    def __call__(self, seed: int):
        first = traffic_mod.first_round(seed)
        return (self._params(weights.weight_key(seed)),
                lambda k: self._data(jnp.int32(first + k)),
                traffic_mod.seed_key(seed, 5))
