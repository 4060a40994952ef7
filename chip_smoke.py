#!/usr/bin/env python3
"""Smoke test of the federated round on a TPU, at full published width.

    python3 chip_smoke.py                # one chip: mamba2_370m, three phases
    python3 chip_smoke.py --four-chips   # star topology on a 4-chip mesh

One chip (the default).  ``mamba2_370m`` at its published width (48 layers,
d_model 1024, vocab 50280; random weights from a seed) trains through the
engine's normal path — ``make_round_engine(model, fl, Topology.sim(2))`` and
``run_rounds``' driver (``RoundRunner.run``) over two compiled chunks of two
rounds — in three phases:

  * ``jax``    — ``topk:0.01>>qsgd:8`` on the pure-JAX wire backend;
  * ``kernel`` — the same spec and seeds with the Pallas kernels compiled by
                 Mosaic (top-k masking + QSGD quantize);
  * ``packed`` — ``topk:0.01>>qsgd:4@fused`` on the kernel backend, so the
                 fused quantize + nibble-pack kernel (``bitpack``) runs too.

Checks: every loss is finite; the kernel phases' compiled programs hold
``tpu_custom_call``; the second chunk of every phase reuses the first
chunk's compilation; ``jax`` and ``kernel`` bill identical ledger bytes every
round and agree on losses and on the parameter update within the tolerances
below; the packed phase's ledger bills C times the bytes its encoder emits.

Four chips (``--four-chips``).  The repo's headline deployment: clients as
mesh slices, compression inside the aggregation collective.  The star
topology on a ``data=4, model=1`` mesh runs ``topk:0.01>>ternary@fused`` on
the kernel backend with error feedback off; its twin is ``Topology.sim(4)``
on device 0 of the same process with the same spec and seeds.  Depth is cut
to 12 layers (width stays published) so that the twin's four clients fit
one chip.  A third program, the twin with one client's weight zeroed, is
a planted dropped-client fault that shows the update comparison can see
one; the three compile concurrently.  Checks: finite losses, equal ledger bytes, losses within
tolerance, star closer to the twin than to the fault by ``STAR_FAULT_MARGIN``,
and in the compiled star program ``tpu_custom_call`` and a u8 all-gather of
one client's payload per chip into all 4.

Everything runs in this one process: a child process could not reach a chip
this process holds.  Without a TPU the script exits non-zero and prints no
result.  The last line of standard output is the JSON result; timings and
memory printed before it are informational.  Round traces go to
``chiprun_out/``.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import math
import os
import sys
import time

REPO = os.path.dirname(os.path.abspath(__file__))

ARCH = "mamba2_370m"
SEED = 0
SEQ, BATCH = 512, 1             # tokens per sequence, sequences per client
CHUNK = 2                       # rounds per compiled scan; two chunks/phase
LOCAL_STEPS, LOCAL_LR = 1, 0.05

# One chip: 2 clients with error feedback compile to 4.20 GB of arguments +
# 11.82 GB of temporaries for a v5e (compiled.memory_analysis() of the
# kernel phase), under the chip's 15.75 GiB; 4 clients do not fit.
ONE_CHIP_CLIENTS = 2
SPEC = "topk:0.01>>qsgd:8"
PACKED_SPEC = "topk:0.01>>qsgd:4@fused"

FOUR_CHIP_CLIENTS = 4
# the sim twin's four clients at 48 layers compile to 17.67 GB of 15.75 GB
# even with error feedback off; 12 layers take 8.93 GB
FOUR_CHIP_LAYERS = 12
FOUR_CHIP_SPEC = "topk:0.01>>ternary@fused"

# Kernel vs jax (DESIGN.md §6): qsgd and topk are bit-exact at stage scope,
# and on a v5e the whole round was too: every one-chip run so far, cold and
# cached compiles, measured exactly 0 difference in losses and updates
# (final minus initial params, over all leaves).  Params are bf16, so any
# difference in an update moves some parameter by at least one bf16 ulp;
# 1e-6 of |update| (~1.6) lets through an ulp on a few near-zero params and
# nothing more.  Losses get 4 f32 ulp.  Bytes are static: exact.
UPDATE_RTOL = 1e-6
LOSS_RTOL = 4 * 2.0 ** -23
# Star vs its sim twin are two XLA programs (local updates partitioned one
# client per chip vs vmapped on one chip); on a v5e their round-0 losses on
# identical params and data differ by ~1e-5 (bf16 accumulation order).
# Params are bf16, so that noise flips the rounding of p + delta, and top-k
# flips supports on top: at 12 layers with error feedback off, star vs twin
# read a relative L2 update difference of 0.116 with no compression (4.1% of
# touched entries touched by one side only) and 0.279 with FOUR_CHIP_SPEC.
# No fixed bound separates that from a fault by reasoning alone, so each run
# reads a planted fault — the twin with client DROP's weight zeroed (0.709
# in that run) — and the star must be at least STAR_FAULT_MARGIN times
# closer to the sound twin than to it.  The margin and the loss bound were
# set before the first run of this check.
STAR_FAULT_MARGIN = 2.0
STAR_LOSS_RTOL = 1e-3
DROP = FOUR_CHIP_CLIENTS - 1


def _fail(msg: str, code: int = 1) -> None:
    print(f"chip_smoke: {msg}", file=sys.stderr, flush=True)
    sys.exit(code)


def _parse():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the star-vs-sim phase on a 4-chip mesh")
    return ap.parse_args()


class Checks:
    def __init__(self):
        self.failed = []

    def expect(self, ok: bool, what: str) -> None:
        print(f"  [{'ok' if ok else 'FAIL'}] {what}", flush=True)
        if not ok:
            self.failed.append(what)


def _host_f32(tree):
    import jax
    import numpy as np
    return [np.asarray(x, np.float32) for x in jax.tree.leaves(tree)]


def _read_spans(path):
    spans = []
    with open(path) as f:
        for line in f:
            rec = json.loads(line)
            if rec.get("type") == "span":
                spans.append((rec["kind"], rec["dur_s"]))
    return spans


def prepare_phase(name, model, fl, topology, n_clients, mesh=None,
                  drop=None):
    """Build the engine, its round runner and the initial state.  ``drop``
    zeroes that client's aggregation weight every round."""
    import jax
    from repro.core.engine import RoundRunner, make_round_engine
    from repro.data.synthetic import FedDataConfig, sample_round
    from repro.obs.trace import Tracer

    engine = make_round_engine(model, fl, topology, mesh=mesh, chunk=SEQ)
    data = FedDataConfig(vocab_size=model.cfg.vocab_size,
                         num_clients=n_clients, seq_len=SEQ,
                         batch_per_client=BATCH, seed=SEED)

    def data_fn(r):
        batch = sample_round(data, jax.random.fold_in(
            jax.random.PRNGKey(SEED + 1), r))
        if drop is not None:
            batch = dict(batch, sizes=batch["sizes"].at[drop].set(0.0))
        return batch

    state = engine.init_fn(jax.random.PRNGKey(SEED))
    if getattr(engine, "state_shardings", None) is not None:
        # the layout RoundRunner.run commits before its first chunk
        state = jax.device_put(state, engine.state_shardings)
    os.makedirs(os.path.join(REPO, "chiprun_out"), exist_ok=True)
    trace = os.path.join(REPO, "chiprun_out", f"chip_smoke_{name}.jsonl")
    tracer = Tracer(trace, meta=dict(arch=model.cfg.name, phase=name,
                                     spec=fl.uplink_compressor,
                                     backend=fl.backend))
    # run_rounds' own driver, kept so that the program that ran can be read
    runner = RoundRunner(engine, data_fn, chunk=CHUNK, tracer=tracer)
    return dict(name=name, runner=runner, state=state, tracer=tracer,
                trace=trace, p0=_host_f32(state.params))


def compile_phases(phases):
    """Lower each phase's chunk program, then compile them all at once: XLA
    compiles outside the GIL, so the host's cores overlap the compiles.
    Each runner's jit keeps its compilation for the run."""
    from concurrent.futures import ThreadPoolExecutor
    lowered = [ph["runner"]._jit.lower(ph["state"], CHUNK) for ph in phases]
    with ThreadPoolExecutor(len(lowered)) as pool:
        list(pool.map(lambda lo: lo.compile(), lowered))


def run_phase(ph):
    """Run 2 chunks of CHUNK rounds as run_rounds does, and bring back what
    the checks compare (on the host)."""
    import jax
    import numpy as np
    runner, tracer = ph.pop("runner"), ph.pop("tracer")
    with tracer.profile():
        state, ms = runner.run(ph.pop("state"), 2 * CHUNK)
    jax.block_until_ready((state, ms))
    tracer.close()
    spans = _read_spans(ph["trace"])
    # the chunk program that ran: lowering the same jit again returns the
    # compilation it holds
    t = time.perf_counter()
    hlo = runner._jit.lower(state, CHUNK).compile().as_text()
    relower_s = time.perf_counter() - t
    out = dict(
        name=ph["name"],
        loss=np.asarray(ms["loss"], np.float64),
        ledger={f.name: np.asarray(getattr(ms["ledger"], f.name), np.float64)
                for f in dataclasses.fields(ms["ledger"])
                if getattr(ms["ledger"], f.name) is not None},
        update=[p - q for p, q in zip(_host_f32(state.params), ph["p0"])],
        spans=spans, hlo=hlo, relower_s=relower_s)
    del state, ms, runner, ph["p0"]
    gc.collect()
    return out


def report_phase(ph, devs):
    kinds = [k for k, _ in ph["spans"]]
    compile_s = sum(d for k, d in ph["spans"] if k == "compile")
    warm = [d for k, d in ph["spans"] if k == "chunk"]
    warm_s = (sum(warm) / (len(warm) * CHUNK)) if warm else float("nan")
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", float("nan"))
             / 2**30 for d in devs]
    print(f"  [info] chunk spans {kinds}; first chunk (compile + run) "
          f"{compile_s:.1f} s; warm round {warm_s:.3f} s; program text "
          f"read back in {ph['relower_s']:.1f} s; peak device memory so far "
          f"{', '.join(f'{p:.2f}' for p in peaks)} GiB", flush=True)
    return kinds


def print_stage_table(fl):
    from repro.core.engine import uplink_pipeline
    from repro.obs.telemetry import stage_sequence
    up = uplink_pipeline(fl)
    print(f"  uplink {up.name}  (FLConfig.backend={fl.backend!r})")
    for s in stage_sequence(up):
        print(f"    stage {type(s).__name__:<8} {s.name:<28} -> "
              f"{getattr(s, 'backend', 'jax')}")


def update_diff(a, b):
    """Relative L2 difference of two updates, |b|, and how their supports
    differ: the share of touched entries that only one update touches, and
    the relative L2 difference on the entries both touch."""
    import numpy as np
    num = den = common = only = either = 0.0
    for x, y in zip(a, b):
        d2 = (x - y) ** 2
        both = (x != 0) & (y != 0)
        num += float(np.sum(d2))
        den += float(np.sum(y ** 2))
        common += float(np.sum(d2[both]))
        nb, ne = int(np.count_nonzero(both)), int(np.count_nonzero(
            (x != 0) | (y != 0)))
        only += ne - nb
        either += ne
    den = math.sqrt(den)
    return dict(rel=math.sqrt(num) / max(den, 1e-30), norm=den,
                only=only / max(either, 1),
                rel_common=math.sqrt(common) / max(den, 1e-30))


def loss_rel_diff(a, b):
    import numpy as np
    return float(np.max(np.abs(a["loss"] - b["loss"])
                        / np.maximum(np.abs(b["loss"]), 1e-30)))


def same_bytes(a, b):
    import numpy as np
    return a["ledger"].keys() == b["ledger"].keys() and all(
        np.array_equal(a["ledger"][k], b["ledger"][k]) for k in b["ledger"])


def compare(checks, a, b, what, loss_rtol, update_rtol=None):
    """Ledger bytes exactly, losses within ``loss_rtol``, and (when given)
    updates within ``update_rtol``; returns the update difference."""
    checks.expect(same_bytes(a, b), f"{what}: identical ledger bytes every "
                  f"round (uplink {b['ledger']['uplink_wire'][0]:.0f} "
                  f"B/round)")
    lerr = loss_rel_diff(a, b)
    checks.expect(lerr <= loss_rtol, f"{what}: per-round losses agree (max "
                  f"rel diff {lerr:.3e} <= {loss_rtol:.3g})")
    u = update_diff(a["update"], b["update"])
    print(f"  [info] {what}: update rel L2 diff {u['rel']:.4e} (|update| "
          f"{u['norm']:.4g}); entries touched by one update only "
          f"{u['only']:.4e}; rel L2 diff on entries both touch "
          f"{u['rel_common']:.4e}", flush=True)
    if update_rtol is not None:
        checks.expect(u["norm"] > 0 and u["rel"] <= update_rtol,
                      f"{what}: parameter updates agree (rel L2 diff "
                      f"{u['rel']:.3e} <= {update_rtol:.3g})")
    return u


def common_checks(checks, ph, kernel):
    import numpy as np
    checks.expect(bool(np.all(np.isfinite(ph["loss"]))),
                  f"{ph['name']}: losses finite {np.round(ph['loss'], 4)}")
    kinds = [k for k, _ in ph["spans"]]
    checks.expect(kinds == ["compile", "chunk"],
                  f"{ph['name']}: second chunk reused the compiled chunk")
    if kernel:
        checks.expect("tpu_custom_call" in ph["hlo"],
                      f"{ph['name']}: compiled round holds tpu_custom_call "
                      f"(x{ph['hlo'].count('tpu_custom_call')})")


def one_chip(checks, dev):
    import jax
    import numpy as np
    from repro.compress.wire_format import payload_nbytes
    from repro.configs.registry import get_arch
    from repro.core.engine import Topology, uplink_pipeline
    from repro.core.types import FLConfig
    from repro.models.model import Model

    model = Model(get_arch(ARCH))
    print(f"model {model.cfg.name}: {model.cfg.num_layers} layers, d_model "
          f"{model.cfg.d_model}, vocab {model.cfg.vocab_size}, "
          f"{model.param_count():,} params; Topology.sim({ONE_CHIP_CLIENTS}),"
          f" seq {SEQ}, batch {BATCH}/client", flush=True)
    fl = lambda spec, backend: FLConfig(
        uplink_compressor=spec, backend=backend, local_steps=LOCAL_STEPS,
        local_lr=LOCAL_LR, seed=SEED)
    phases = {}
    for name, spec, backend in (("jax", SPEC, "jax"),
                                ("kernel", SPEC, "kernel"),
                                ("packed", PACKED_SPEC, "kernel")):
        print(f"phase {name}: {spec} on the {backend} backend", flush=True)
        print_stage_table(fl(spec, backend))
        ph = run_phase(prepare_phase(name, model, fl(spec, backend),
                                     Topology.sim(ONE_CHIP_CLIENTS),
                                     ONE_CHIP_CLIENTS))
        report_phase(ph, [dev])
        common_checks(checks, ph, kernel=backend == "kernel")
        phases[name] = ph
    compare(checks, phases["kernel"], phases["jax"], "kernel vs jax",
            LOSS_RTOL, UPDATE_RTOL)
    up = uplink_pipeline(fl(PACKED_SPEC, "kernel"))
    emitted = ONE_CHIP_CLIENTS * sum(
        payload_nbytes(up, int(np.prod(leaf.shape)))
        for leaf in jax.tree.leaves(model.abstract_params()))
    # the ledger keeps f32 round totals: the per-client term rounded to f32
    # times the client count, so it may sit up to one f32 ulp of the total
    # (4 B at ~38 MB) from the exact count
    billed = phases["packed"]["ledger"]["uplink_wire"]
    ulp = float(np.spacing(np.float32(emitted)))
    checks.expect(bool(np.all(np.abs(billed - emitted) <= ulp)),
                  f"packed: the run's ledger bills what the encoder emits "
                  f"({ONE_CHIP_CLIENTS} clients x payload = {emitted} B; "
                  f"billed {sorted(set(billed.tolist()))} B/round, within "
                  f"{ulp:.0f} B)")


def client_gathers(hlo, n):
    """Lines of an HLO text that all-gather u8 data along dim 0 over groups
    of ``n`` devices into ``n`` rows: one row (a client's packed payload)
    from each chip."""
    import re
    hits = []
    for line in hlo.splitlines():
        m = re.search(r"=\s*(.*?)\s+all-gather(?:-start)?\(", line)
        if m is None or "dimensions={0}" not in line:
            continue
        rows = [int(d) for d in re.findall(r"u8\[(\d+)", m.group(1))]
        g = re.search(r"replica_groups=\{\{([\d,]+)\}", line)
        if g is not None:
            group = len(g.group(1).split(","))
        else:                                   # iota form [groups,size]<=
            g = re.search(r"replica_groups=\[\d+,(\d+)\]", line)
            group = int(g.group(1)) if g else 0
        if group == n and n in rows:
            hits.append(line)
    return hits


def four_chips(checks, devs, spec=FOUR_CHIP_SPEC, error_feedback=False):
    """Star on a data=4 mesh vs its Topology.sim(4) twin on device 0, plus
    the twin with client DROP zeroed; returns the update differences."""
    import jax
    from jax.sharding import AxisType
    from repro.configs.registry import get_arch
    from repro.core.engine import Topology
    from repro.core.types import FLConfig
    from repro.models.model import Model

    if len(devs) != 4:
        _fail(f"--four-chips needs 4 devices, JAX sees {len(devs)}")
    cfg = dataclasses.replace(get_arch(ARCH), num_layers=FOUR_CHIP_LAYERS)
    model = Model(cfg)
    mesh = jax.make_mesh((FOUR_CHIP_CLIENTS, 1), ("data", "model"),
                         devices=devs, axis_types=(AxisType.Auto,) * 2)
    fl = FLConfig(uplink_compressor=spec, backend="kernel",
                  error_feedback=error_feedback, local_steps=LOCAL_STEPS,
                  local_lr=LOCAL_LR, seed=SEED)
    print(f"model {cfg.name} cut to {cfg.num_layers} layers (d_model "
          f"{cfg.d_model}, vocab {cfg.vocab_size}), {model.param_count():,} "
          f"params; {FOUR_CHIP_CLIENTS} clients, seq {SEQ}, batch {BATCH}",
          flush=True)
    print_stage_table(fl)
    print(f"compiling star on mesh {dict(mesh.shape)} over "
          f"{[d.id for d in devs]}, its Topology.sim({FOUR_CHIP_CLIENTS}) "
          f"twin and the twin with client {DROP}'s weight zeroed (both on "
          f"the default device {devs[0].id})", flush=True)
    phases = [prepare_phase("star", model, fl, Topology.star(),
                            FOUR_CHIP_CLIENTS, mesh=mesh)]
    phases += [prepare_phase(name, model, fl, Topology.sim(FOUR_CHIP_CLIENTS),
                             FOUR_CHIP_CLIENTS, drop=drop)
               for name, drop in (("sim4", None), ("sim4_drop", DROP))]
    t = time.perf_counter()
    compile_phases(phases)
    print(f"  [info] three programs lowered and compiled in "
          f"{time.perf_counter() - t:.1f} s", flush=True)
    done = {}
    for ph in phases:
        print(f"phase {ph['name']}", flush=True)
        done[ph["name"]] = out = run_phase(ph)
        report_phase(out, devs)
        common_checks(checks, out, kernel=True)
    star = done["star"]
    ag = client_gathers(star["hlo"], FOUR_CHIP_CLIENTS)
    checks.expect(bool(ag), f"star: compiled program all-gathers u8 "
                  f"payloads, one client per chip into {FOUR_CHIP_CLIENTS} "
                  f"({len(ag)} lines)")
    sound = compare(checks, star, done["sim4"], "star vs sim",
                    STAR_LOSS_RTOL)
    fault = update_diff(star["update"], done["sim4_drop"]["update"])
    print(f"  [info] star vs sim with client {DROP} dropped: update rel L2 "
          f"diff {fault['rel']:.4e}; entries touched by one update only "
          f"{fault['only']:.4e}; losses max rel diff "
          f"{loss_rel_diff(star, done['sim4_drop']):.3e}", flush=True)
    ratio = fault["rel"] / sound["rel"] if sound["rel"] else math.inf
    checks.expect(
        sound["norm"] > 0 and STAR_FAULT_MARGIN * sound["rel"] <= fault["rel"],
        f"star vs sim: updates agree {ratio:.3g}x closer than with a dropped "
        f"client (rel L2 {sound['rel']:.3e} vs {fault['rel']:.3e}; needs >= "
        f"{STAR_FAULT_MARGIN}x)")
    return dict(sound=sound, fault=fault,
                loss_sound=loss_rel_diff(star, done["sim4"]),
                loss_fault=loss_rel_diff(star, done["sim4_drop"]))


def main():
    args = _parse()
    if not os.path.isdir(os.path.join(REPO, "src", "repro", "core")):
        _fail("src/repro is not beside this script; run it from a checkout",
              code=2)
    sys.path.insert(0, os.path.join(REPO, "src"))
    from repro.launch import compile_cache
    cache_dir = compile_cache.enable()

    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        _fail(f"no TPU: JAX sees platform {devs[0].platform!r}", code=3)
    print(f"devices: {len(devs)} x {devs[0].device_kind}; jax "
          f"{jax.__version__}; compile cache {cache_dir}", flush=True)
    checks = Checks()
    t0 = time.perf_counter()
    if args.four_chips:
        four_chips(checks, devs)
    else:
        one_chip(checks, devs[0])
    print(f"[info] wall time {time.perf_counter() - t0:.1f} s", flush=True)
    if checks.failed:
        _fail(f"{len(checks.failed)} check(s) failed: {checks.failed}")
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}), flush=True)


if __name__ == "__main__":
    main()
