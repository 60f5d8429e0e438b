"""Runner of the ``sim_train`` traffic kind: AQ-SGD (or fp32) training
of a pipeline of stages simulated in one program on one chip, through
the program's ``training/simulated.py::train_step``.

One run, in order:

1. set-up — weights from the seed on the device in one jitted call
   (`reference.<family>.init_params`, the program's parameter layout),
   the train state around them, the traffic's corpus; then the first
   epoch (every sample once, so that every later step takes the AQ-SGD
   delta path) through the same step call and feed the window uses,
   and (AQ-SGD) the first step of the second epoch, the first that
   revisits samples and so the first on the window's delta path.  The
   readings are taken on the way: the losses of the first three steps,
   the first gradient as AdamW holds it after one step (mu / (1 - b1)),
   the parameters' change after three steps, the messages the first
   step stored (the activations of a first visit), and those the
   revisiting step found and stored (the previous message, and it plus
   the decoded low-bit delta).
2. the window — whole steps for ``seconds``, each a batch fed from the
   host, the jitted step, and the loss read back as a float: the
   per-step loop of ``launch/runner.py::run_sim_training``, copied
   because that launcher builds its own state and starts at step 0, so
   it cannot be handed this one.  It ends with ``block_until_ready``.
3. the peak memory, the trace reduction (``--trace 1``), then the
   program's state is freed and the plain reference runs the compared
   steps from the same seed (`harness.compare`): the first three, and
   (AQ-SGD) on through the revisiting step.
"""
from __future__ import annotations

import functools
import gc
import importlib
import math
import shutil
import tempfile
import time
from dataclasses import fields

import numpy as np

from harness import compare, device, hlo, metrics, trace as tr, traffic

STEPS_COMPARED = 3


def seed_key(seed: int):
    """A PRNG key from a seed of any size (two 32-bit words)."""
    import jax
    k = jax.random.PRNGKey(0)
    k = jax.random.fold_in(k, seed & 0xFFFFFFFF)
    return jax.random.fold_in(k, seed >> 32)


def model_config(c: dict):
    """The program's ModelConfig for the configuration file ``c``: its
    registered configuration with every size the file gives; each must
    then read back as the file has it."""
    from repro.configs.base import ModelConfig, get_config
    names = {f.name for f in fields(ModelConfig)} - {"name", "source"}
    kw = {k: v for k, v in c.items() if k in names}
    mcfg = get_config(c["program_config"]).with_(**kw)
    for k, v in kw.items():
        if getattr(mcfg, k) != v:
            raise ValueError(f"configuration key {k}: file {v!r}, "
                             f"program {getattr(mcfg, k)!r}")
    return mcfg


def train_config(t: dict):
    from repro.comm.config import CommConfig, PlaneConfig
    from repro.optim.adamw import AdamWConfig
    from repro.training import simulated as sim
    if t["mode"] == "fp32":
        comm = CommConfig(mode="fp32")
    else:
        st = bool(t.get("stochastic", True))
        comm = CommConfig(mode=t["mode"],
                          fw=PlaneConfig(bits=t["fw_bits"], stochastic=st),
                          bw=PlaneConfig(bits=t["bw_bits"], stochastic=st))
    return sim.SimTrainConfig(num_stages=t["stages"], remat=t["remat"],
                              comm=comm,
                              optimizer=AdamWConfig(**t["optimizer"]))


def _named(tree) -> dict:
    import jax
    from reference.common import leaf_names
    return {n: np.asarray(v) for n, v in
            zip(leaf_names(tree), jax.tree.leaves(tree))}


def _feed(b: dict):
    import jax.numpy as jnp
    return {k: jnp.asarray(v) for k, v in b.items()}


class Program:
    """The compiled step with its state, built from the seed; set-up
    drives it through the first epoch and the window goes on with it.
    ``dtype`` replaces the configuration's compute dtype (the control
    runs the program's own bfloat16 path)."""

    def __init__(self, cell, seed: int, log=print, dtype: str = None):
        import jax
        from repro.training import simulated as sim
        self.cell, self.seed, self.log = cell, seed, log
        c, t = cell.config, cell.traffic
        self.family = c["family"]
        self.ref = importlib.import_module(f"reference.{self.family}")
        self.batch, self.seq = t["batch"], t["seq"]
        self.samples = int(cell.own["samples"])
        self.tokens_per_step = self.batch * self.seq
        self.flops_per_token = importlib.import_module(
            f"flops.{self.family}").flops_per_token(c, self.seq)
        self.mcfg = model_config(c)
        if dtype:
            self.mcfg = self.mcfg.with_(dtype=dtype)
        self.tcfg = train_config(t)
        self.aqsgd = self.tcfg.comm.mode == "aqsgd"
        if self.samples < STEPS_COMPARED * self.batch:
            raise ValueError(f"{self.samples} samples: the first "
                             f"{STEPS_COMPARED} steps need distinct rows")
        self.k_w, self.k_run = jax.random.split(seed_key(seed))
        tokens = traffic.corpus(seed, samples=self.samples, seq=self.seq,
                                vocab=c["vocab_size"], **t["corpus"])
        self.epochs = traffic.Epochs(tokens, self.batch, seed)
        mcfg, tcfg, ref = self.mcfg, self.tcfg, self.ref
        n, s = self.samples, self.seq

        @jax.jit
        def init(k):
            st = sim.init_train_state(mcfg, tcfg, n, s, k)
            st["params"] = ref.init_params(c, k)
            return st
        self.state = init(self.k_w)
        want = jax.eval_shape(lambda: sim.init_train_state(
            mcfg, tcfg, n, s, self.k_w)["params"])
        got = jax.eval_shape(lambda: self.state["params"])
        if jax.tree.structure(want) != jax.tree.structure(got) or any(
                a.shape != b.shape or a.dtype != b.dtype for a, b in
                zip(jax.tree.leaves(want), jax.tree.leaves(got))):
            raise ValueError("reference weights do not match the "
                             "program's parameter layout")
        # the first step of the second epoch revisits samples: the
        # first whose messages pass through the forward delta codec
        self.revisit = self.epochs.steps_per_epoch if self.aqsgd else None
        self.batches = []              # the compared steps' host batches
        self.readings = {"loss": []}
        self.step_times = []
        self.steps = 0
        self.window_s = None
        self.trace = None
        self.ops = None

    def step(self, i: int, b: dict) -> float:
        """Step ``i`` of the program on host batch ``b``: feed, jitted
        step, loss read back as a float."""
        import jax
        from repro.training import simulated as sim
        with jax.profiler.TraceAnnotation("bench.feed"):
            fed = _feed(b)
        with jax.profiler.TraceAnnotation("bench.train_step"):
            self.state, met = sim.train_step(
                self.state, fed, jax.random.fold_in(self.k_run, i),
                mcfg=self.mcfg, tcfg=self.tcfg)
            self._last = fed
            return float(met["loss"])

    def first_epoch(self, feed=None):
        """The whole first epoch, and (AQ-SGD) the revisiting step,
        with the readings of the compared steps.  ``feed`` may alter
        each host batch before the program sees it."""
        import jax
        import jax.numpy as jnp
        from reference.common import layer_norms
        b1 = float(self.cell.traffic["optimizer"]["b1"])
        ref, c = self.ref, self.cell.config
        grad_fn = jax.jit(lambda mu: jax.tree.map(
            lambda x: x / (1.0 - b1), layer_norms(mu)))
        change_fn = jax.jit(lambda p, k: layer_norms(jax.tree.map(
            jnp.subtract, p, ref.init_params(c, k))))
        r = self.readings
        last = STEPS_COMPARED - 1 if self.revisit is None else self.revisit
        for i in range(max(self.epochs.steps_per_epoch, last + 1)):
            b = self.epochs.next()
            if i <= last:
                self.batches.append(b)
            if i == self.revisit:
                r["revisit_prev"] = np.asarray(
                    self.state["buffers"]["m"][:, b["sample_ids"]])
            loss = self.step(i, feed(b) if feed else b)
            if i < STEPS_COMPARED:
                r["loss"].append(loss)
            if i == 0:
                r["grad"] = _named(grad_fn(self.state["opt"]["mu"]))
                if self.aqsgd:
                    r["bounds"] = np.asarray(
                        self.state["buffers"]["m"][:, b["sample_ids"]])
            if i == STEPS_COMPARED - 1:
                r["change"] = _named(change_fn(self.state["params"],
                                               self.k_w))
            if i == self.revisit:
                r["revisit_bounds"] = np.asarray(
                    self.state["buffers"]["m"][:, b["sample_ids"]])
        jax.block_until_ready(self.state)

    def window(self, seconds: float) -> list:
        """Whole steps until ``seconds`` have passed; their losses."""
        import jax
        losses = []
        i0 = self.epochs.step
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.window"):
            while True:
                ts = time.perf_counter()
                i = self.epochs.step
                with jax.profiler.StepTraceAnnotation("bench.step",
                                                      step_num=i):
                    losses.append(self.step(i, self.epochs.next()))
                self.step_times.append(time.perf_counter() - ts)
                if time.perf_counter() - t0 >= seconds:
                    break
            jax.block_until_ready(self.state)
        self.window_s = time.perf_counter() - t0
        self.steps = self.epochs.step - i0
        return losses

    def step_hlo(self) -> str:
        """The optimised HLO of the step as it ran (from the
        compilation cache)."""
        import jax
        from repro.training import simulated as sim
        return sim.train_step.lower(
            self.state, self._last, jax.random.fold_in(self.k_run, 0),
            mcfg=self.mcfg, tcfg=self.tcfg).compile().as_text()

    def free(self):
        self.state = self._last = None
        gc.collect()


def run(cell, *, seed: int, seconds: float, trace: bool, t_start: float,
        devices, log=print) -> dict:
    import jax
    P = Program(cell, seed, log)
    P.first_epoch()
    setup_s = time.perf_counter() - t_start
    log(f"set-up {setup_s:.3f} s: {P.epochs.step} steps over "
        f"{P.samples} samples")

    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    if trace:
        jax.profiler.start_trace(trace_dir)
    losses = P.window(seconds)
    if trace:
        jax.profiler.stop_trace()
    tokens_per_s = P.steps * P.tokens_per_step / P.window_s
    dev = device.record(devices)
    P.peaks = device.peaks(dev["kind"])
    P.chips = len(devices)
    failed = sum(not math.isfinite(x) for x in losses)
    log(f"window {P.window_s:.3f} s: {P.steps} steps, {tokens_per_s:.1f} "
        f"tokens/s, peak {dev['memory_peak_bytes']} bytes")

    result = {"correct": False, "attempted": P.steps, "failed": failed}
    if trace:
        P.trace = tr.load(trace_dir)
        shutil.rmtree(trace_dir, ignore_errors=True)
        log("trace layout: " + "; ".join(f"{p} / {ln}: {n}" for p, ln, n
                                         in P.trace.layout))
        P.ops = hlo.op_table(P.step_hlo())
        vals = metrics.read_all(cell.per_layer, P)
        for m in cell.per_layer:
            if vals.get(m["name"]) is None:
                log(f"bench: per-layer metric {m['name']} read nothing "
                    f"in this run; it is left out of the result line")
        result["metrics"] = {m["name"]: {"value": vals[m["name"]],
                                         "unit": m["unit"]}
                             for m in cell.per_layer
                             if vals.get(m["name"]) is not None}
        dev["busy_s"], dev["window_s"] = metrics.busy_window(P)
        result["breakdown"] = metrics.breakdown(P)
    else:
        e2e = {"tokens_per_s": tokens_per_s,
               "mfu": 100.0 * P.flops_per_token * tokens_per_s
               / (P.chips * P.peaks["bf16_flops_per_s"]),
               "setup_s": setup_s}
        result["metrics"] = {m["name"]: {"value": e2e[m["name"]],
                                         "unit": m["unit"]}
                             for m in cell.end_to_end}
    result["device"] = dev

    P.free()
    ref = reference(cell, seed, P.batches, log)
    values = gaps(P.readings, ref, P.aqsgd)
    for k in sorted(set(values) - set(cell.own["limits"])):
        log(f"reading {k} {values[k]!r} (no limit: not compared)")
    ok, checks = compare.judge(values, cell.own["limits"])
    result["correct"] = bool(ok and failed == 0)
    result["checks"] = checks
    return result


def reference(cell, seed: int, batches: list, log=print,
              precision: str = None) -> dict:
    """The plain reference through the compared steps, from the seed:
    its losses and first-gradient norms, its change norms after three
    steps, the activations at the boundaries in the first and the last
    step of ``batches``, and those of the last step's samples at their
    visit before.  Its matmuls
    run at ``precision``, by default the configuration's
    ``matmul_precision``."""
    import jax
    import jax.numpy as jnp
    from reference.common import adamw, layer_norms
    c, t = cell.config, cell.traffic
    ref = importlib.import_module(f"reference.{c['family']}")
    stages, opt = t["stages"], t["optimizer"]
    k_w = jax.random.split(seed_key(seed))[0]

    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def ref_step(params, ostate, batch):
        (loss, bounds), grads = jax.value_and_grad(
            lambda p: ref.loss(c, p, batch, stages), has_aux=True)(params)
        params, ostate = adamw(opt, params, grads, ostate)
        return params, ostate, loss, bounds, layer_norms(grads)

    with jax.default_matmul_precision(precision
                                      or c["matmul_precision"]):
        return _reference_steps(c, ref, ref_step, k_w, batches, log)


def _reference_steps(c, ref, ref_step, k_w, batches, log) -> dict:
    import jax
    import jax.numpy as jnp
    from reference.common import layer_norms
    t0 = time.perf_counter()
    change_fn = jax.jit(lambda p, k: layer_norms(jax.tree.map(
        jnp.subtract, p, ref.init_params(c, k))))
    params = jax.jit(lambda k: ref.init_params(c, k))(k_w)
    ostate = (jnp.zeros((), jnp.float32),
              jax.tree.map(jnp.zeros_like, params),
              jax.tree.map(jnp.zeros_like, params))
    out = {"loss": []}
    last_ids = [int(x) for x in batches[-1]["sample_ids"]]
    prev = {}                  # the last step's samples at their visit before
    for i, b in enumerate(batches):
        params, ostate, loss, bounds, gnorm = ref_step(params, ostate,
                                                       _feed(b))
        if i < len(batches) - 1:
            for row, sid in enumerate(int(x) for x in b["sample_ids"]):
                if sid in last_ids:
                    prev[sid] = [np.asarray(x[row]) for x in bounds]
        if i < STEPS_COMPARED:
            out["loss"].append(float(loss))
        if i == 0:
            out["grad"] = _named(gnorm)
            out["bounds"] = bounds
        if i == STEPS_COMPARED - 1:
            out["change"] = _named(change_fn(params, k_w))
        if i == len(batches) - 1:
            out["last_bounds"] = bounds
    if len(prev) == len(last_ids):
        out["prev_bounds"] = [np.stack([prev[s][j] for s in last_ids])
                              for j in range(len(prev[last_ids[0]]))]
    del params, ostate
    log(f"reference {time.perf_counter() - t0:.3f} s: losses "
        f"{out['loss']}")
    return out


def gaps(prog: dict, ref: dict, aqsgd: bool) -> dict:
    """The compared numbers of `harness.compare` from the two sides'
    readings."""
    keep = compare.moving_leaves(ref["grad"])
    out = {"loss_gap": compare.loss_gap(prog["loss"], ref["loss"]),
           "grad_gap": compare.leaf_gap(prog["grad"], ref["grad"]),
           "change_gap": compare.leaf_gap(prog["change"], ref["change"],
                                          keep)}
    if aqsgd:
        out["boundary_gap"] = compare.message_gap(prog["bounds"],
                                                  ref["bounds"])
        out["bf16_share_gap"] = compare.bf16_share_gap(prog["bounds"],
                                                       ref["bounds"])
        out["revisit_gap"] = compare.message_gap(prog["revisit_bounds"],
                                                 ref["last_bounds"])
        out["delta_gap"] = compare.delta_gap(
            prog["revisit_bounds"], prog["revisit_prev"],
            ref["last_bounds"], ref["prev_bounds"])
    return out
