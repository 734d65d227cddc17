"""The training loop a user writes, measured.

Set-up, each part under a span of the benchmark's own: ``hvd.init()`` first,
as a user calls it, and the devices (where the TPU runtime starts), then the
mesh; parameters made on the device from the seed in one jitted call with
``out_shardings``; the check of the system against the plain reference
(while only the parameters occupy the device): the loss in every run, the
gradients in every run or in the traced run only, as the cell's traffic file
says; the optimizer state, one jitted call; lowering and compiling the one
step shape the cell uses; warm-up steps.  Then the window: one call of the
compiled step per batch, a new batch every step drawn from the seed and put
on the mesh one step ahead of the device, at most two steps in flight, a
step's completion taken when its loss is ready.  The window opens at a
completion and closes at the first completion ``--seconds`` later, so it
holds whole steps only; throughput is the tokens of those steps over the
time between the two completions.
"""

from __future__ import annotations

import collections
import math
import shutil
import time

import numpy as np

from benchmark import loader
from benchmark.spans import host_span
from benchmark.trace import reduce as trace_reduce

WARMUP_STEPS = 3
TRACE_STEPS = 5
MAX_IN_FLIGHT = 2
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
TRACE_DIR = loader.REPO_ROOT / ".benchmark_trace"


class CompileCounter:
    """Counts backend compilations (cache loads included) while ``on``."""

    def __init__(self, monitoring):
        self.on = False
        self.count = 0
        monitoring.register_event_duration_secs_listener(self._event)

    def _event(self, event, _duration, **_kw):
        if self.on and event == COMPILE_EVENT:
            self.count += 1


class Window:
    """The completions of steps on the host clock, the window over them
    and, in a traced run, the trace taken in its middle.

    The window opens at the completion of the last warm-up step and closes
    at the first completion ``seconds`` later (in a traced run: not before
    the trace is written).  The trace starts at a completion half-way and
    stops ``trace_steps`` + 2 completions on, so that it holds at least
    ``trace_steps`` whole steps between the two it cuts."""

    def __init__(self, *, seconds, warmup_steps, compiles, trace_dir,
                 trace_steps):
        self.seconds, self.warmup_steps = seconds, warmup_steps
        self.compiles = compiles
        self.trace_dir, self.trace_steps = trace_dir, trace_steps
        self.done_at, self.losses = [], []        # per completed step
        self.t_open = self.t_close = None
        self.i_open = self.i_close = None
        self.trace_from = None        # completions when the trace began
        self.tracing = False

    @property
    def traced(self) -> bool:
        return self.trace_from is not None and not self.tracing

    def completed(self, loss: float) -> None:
        now = time.perf_counter()
        self.done_at.append(now)
        self.losses.append(loss)
        n_done = len(self.done_at)
        if self.t_open is None:
            if n_done == self.warmup_steps:
                self.t_open, self.i_open = now, n_done
                self.compiles.on = True
            return
        elapsed = now - self.t_open
        if self.trace_dir and self.trace_from is None:
            if elapsed >= self.seconds / 2:
                import jax
                shutil.rmtree(self.trace_dir, ignore_errors=True)
                options = jax.profiler.ProfileOptions()
                options.python_tracer_level = 0
                options.host_tracer_level = 2
                options.enable_hlo_proto = False
                jax.profiler.start_trace(self.trace_dir,
                                         profiler_options=options)
                self.trace_from, self.tracing = n_done, True
        elif self.tracing and n_done - self.trace_from >= self.trace_steps + 2:
            self.stop_trace()
        if elapsed >= self.seconds and (not self.trace_dir or self.traced):
            self.close(now)

    def stop_trace(self) -> None:
        import jax
        if self.tracing:
            jax.profiler.stop_trace()
            self.tracing = False

    def close(self, now: float) -> None:
        if self.t_close is None:
            self.t_close, self.i_close = now, len(self.done_at)
            self.compiles.on = False

    def step_ms(self) -> list[float]:
        done = self.done_at[self.i_open - 1:self.i_close]
        return [1e3 * (b - a) for a, b in zip(done, done[1:])]

    def steps_per_s(self) -> float | None:
        """The window's whole steps over the time between the two
        completions that bound it: a slow tail, a periodic stall or a host
        sync inside the window costs here as it costs a user.  The median of
        ``step_ms`` is the view that leaves such things out."""
        n_steps = self.i_close - self.i_open
        seconds = self.t_close - self.t_open
        return n_steps / seconds if n_steps and seconds > 0 else None


def state_shardings(state_shapes, param_shardings, replicated):
    """Shardings for an optimizer state: a subtree shaped like the
    parameters (a moment) is sharded like them, anything else (a step
    count) is replicated."""
    import jax
    pdef = jax.tree_util.tree_structure(param_shardings)

    def like_params(x):
        return jax.tree_util.tree_structure(x) == pdef

    return jax.tree_util.tree_map(
        lambda sub: param_shardings if like_params(sub) else replicated,
        state_shapes, is_leaf=like_params)


def make_optimizer(spec: dict):
    import optax
    if spec["name"] != "adamw":
        raise loader.BenchmarkError(f"unknown optimizer {spec['name']!r}")
    return optax.adamw(spec["learning_rate"])


GRADIENT_CHECKS = ("every_run", "traced_run")


def check_against_reference(fam, ref, mesh, params, batch, data_sharding,
                            say, *, gradients: bool) -> bool:
    """The system's loss (the model's own loss function on the cell's mesh,
    as the trainer computes it) against the plain reference's on one device,
    at the highest matmul precision around the whole reference call; with
    ``gradients`` both under ``jax.value_and_grad``, and every gradient leaf
    compared too."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    one = SingleDeviceSharding(mesh.devices.flat[0])
    on_mesh = tuple(jax.make_array_from_process_local_data(
        data_sharding, x, x.shape) for x in batch)
    ref_params = fam.to_reference(jax.device_put(params, one))
    ref_batch = tuple(jax.device_put(x, one) for x in batch)
    kwargs = fam.reference_args()
    sys_fn = fam.loss_fn(mesh)

    def ref_fn(p, *b):
        return ref.loss(p, *b, **kwargs)

    if gradients:
        sys_fn, ref_fn = jax.value_and_grad(sys_fn), jax.value_and_grad(ref_fn)
    sys_out = jax.jit(sys_fn)(params, *on_mesh)
    with jax.default_matmul_precision("highest"):
        ref_out = jax.jit(ref_fn)(ref_params, *ref_batch)

    tol = ref.TOLERANCES
    errs, said = {}, "gradients not compared in this run"
    if gradients:
        (sys_out, sys_grads), (ref_out, ref_grads) = sys_out, ref_out
        sys_grads = fam.to_reference(jax.device_put(sys_grads, one))
        rel_l2 = jax.jit(lambda a, b: jax.tree_util.tree_map(
            lambda x, y: jnp.sqrt(jnp.sum((x - y) ** 2) / jnp.sum(y ** 2)),
            a, b))
        errs = {jax.tree_util.keystr(k): float(v) for k, v in
                jax.tree_util.tree_leaves_with_path(
                    rel_l2(sys_grads, ref_grads))}
        worst = max(errs, key=errs.get)
        said = (f"gradients' relative L2 error over {len(errs)} leaves: "
                f"worst {errs[worst]:.3e} at {worst} (allowed "
                f"{tol['grad_rel_l2']:.0e})")
    sys_loss, ref_loss = float(sys_out), float(ref_out)
    ok = (math.isfinite(sys_loss) and math.isfinite(ref_loss)
          and abs(sys_loss - ref_loss) <= tol["loss_abs"]
          and all(math.isfinite(e) and e <= tol["grad_rel_l2"]
                  for e in errs.values()))
    say(f"reference check on {batch[0].shape[0]} sequence(s): loss system "
        f"{sys_loss:.6f} reference {ref_loss:.6f} (|diff| "
        f"{abs(sys_loss - ref_loss):.2e}, allowed {tol['loss_abs']:.0e}); "
        f"{said} -> {'ok' if ok else 'FAILED'}")
    return ok


def run(*, cell, find_devices, spans, seed, seconds, trace, rehearsal,
        say) -> dict:
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    import horovod_tpu as hvd
    from horovod_tpu.parallel.mesh import create_mesh

    config, spec = cell["config"], cell["traffic"]
    chips = cell["entry"]["chips"]
    warmup_steps = WARMUP_STEPS if rehearsal is None else rehearsal.warmup_steps
    trace_steps = TRACE_STEPS if rehearsal is None else rehearsal.trace_steps
    gradient_check = spec.get("gradient_check", GRADIENT_CHECKS[0])
    if gradient_check not in GRADIENT_CHECKS:
        raise loader.BenchmarkError(
            f"gradient_check is {gradient_check!r}, not one of "
            f"{GRADIENT_CHECKS}")
    compiles = CompileCounter(jax.monitoring)

    # The order a user's program takes: hvd.init() first.  Whichever of the
    # two calls touches JAX's backend first starts the TPU runtime, which is
    # nearly all of this span and nothing a program can change.
    with spans.span("setup_backend"):
        hvd.init()
    try:
        with spans.span("setup_backend"):
            dev = find_devices()
        family_mod = loader.load_code("families", config["family"])
        ref = loader.load_code("reference", config["family"])
        fam = family_mod.Family(config, spec["mesh"])
        if math.prod(fam.mesh_shape.values()) != chips:
            raise loader.BenchmarkError(
                f"mesh {fam.mesh_shape} does not span {chips} chip(s)")
        mesh = create_mesh(fam.mesh_shape, devices=dev["devices"])
        global_batch = spec["global_batch"]
        if global_batch % fam.dp:
            raise loader.BenchmarkError(
                f"global_batch {global_batch} does not divide over "
                f"dp={fam.dp}")
        spans.seconds["setup_init"] = (spans.since_start()
                                       - spans.seconds["setup_backend"])
        say(f"{cell['name']}: {dev['kind']!r} x{chips} mesh {fam.mesh_shape} "
            f"global_batch {global_batch} compile cache "
            f"{jax.config.jax_compilation_cache_dir}")

        replicated = NamedSharding(mesh, P())
        data_sharding = NamedSharding(mesh, P("dp"))
        param_shardings = jax.tree_util.tree_map(
            lambda s: NamedSharding(mesh, s), fam.param_specs(),
            is_leaf=lambda x: isinstance(x, P))
        opt = make_optimizer(config["optimizer"])

        with spans.span("setup_state"):
            params = jax.jit(fam.init_params, out_shardings=param_shardings)(
                jax.random.PRNGKey(seed))
            jax.block_until_ready(params)

        def draw(step_index: int, n_seq: int, stream: int = 0):
            return fam.draw_batch(
                np.random.default_rng([seed, stream, step_index]), n_seq)

        # A stream of its own, the family's number of sequences for each
        # data-parallel rank.  What every run pays is under ``setup_check``;
        # where the traffic file keeps the gradients for the traced run,
        # that run makes the same check first and theirs after it.
        check_batch = draw(0, fam.dp * fam.check_seqs_per_rank, stream=1)
        with spans.span("setup_check"):
            checked = check_against_reference(
                fam, ref, mesh, params, check_batch, data_sharding, say,
                gradients=gradient_check == "every_run")
        if trace and gradient_check == "traced_run":
            with spans.span("setup_check_gradients"):
                checked = check_against_reference(
                    fam, ref, mesh, params, check_batch, data_sharding, say,
                    gradients=True) and checked

        with spans.span("setup_state"):
            state_shapes = jax.eval_shape(opt.init, params)
            opt_state = jax.jit(opt.init, out_shardings=state_shardings(
                state_shapes, param_shardings, replicated))(params)
            jax.block_until_ready(opt_state)

        def put(batch):
            return tuple(jax.make_array_from_process_local_data(
                data_sharding, x, x.shape) for x in batch)

        with host_span("bench.draw_batch"):
            first = draw(0, global_batch)
        with host_span("bench.put_batch"):
            next_batch = put(first)
        with spans.span("setup_compile"):
            compiled = fam.train_step(mesh, opt).lower(
                params, opt_state, *next_batch).compile()
        mem = compiled.memory_analysis()
        say(f"compiled step, bytes per device: arguments "
            f"{mem.argument_size_in_bytes} temporaries "
            f"{mem.temp_size_in_bytes} peak {mem.peak_memory_in_bytes}")

        # -- the loop ---------------------------------------------------------
        tokens_per_step = global_batch * fam.tokens_per_seq
        trace_dir = str(TRACE_DIR / cell["name"])
        window = Window(seconds=seconds, warmup_steps=warmup_steps,
                        compiles=compiles, trace_steps=trace_steps,
                        trace_dir=trace_dir if trace else None)
        in_flight = collections.deque()
        issued = raised = 0
        while window.t_close is None:
            try:
                with host_span("bench.dispatch_step"):
                    params, opt_state, loss = compiled(params, opt_state,
                                                       *next_batch)
            except Exception as e:        # a step that raised is a failure
                say(f"step {issued} raised {e!r}")
                raised += 1
                break
            in_flight.append(loss)
            issued += 1
            with host_span("bench.draw_batch"):
                batch = draw(issued, global_batch)
            with host_span("bench.put_batch"):
                next_batch = put(batch)
            if len(in_flight) == MAX_IN_FLIGHT:
                with host_span("bench.wait_loss"):
                    value = float(in_flight.popleft())
                window.completed(value)
        window.stop_trace()
        while in_flight:                  # drain; outside the window
            float(in_flight.popleft())
        if window.t_open is None:
            raise loader.BenchmarkError("a step raised during warm-up")
        window.close(time.perf_counter())  # if the loop broke on a raise
    finally:
        hvd.shutdown()

    say(f"first losses {[round(x, 4) for x in window.losses[:4]]}")
    t_open = window.t_open
    window_losses = window.losses[window.i_open:window.i_close]
    step_ms = window.step_ms()
    n_steps = len(window_losses)
    failed = sum(1 for x in window_losses if not math.isfinite(x)) + raised
    window_s = window.t_close - t_open
    steps_per_s = window.steps_per_s()
    tokens_per_s_per_chip = (steps_per_s * tokens_per_step / chips
                             if steps_per_s else None)
    peaks = dev["peaks"]
    end_to_end = {"setup_s": t_open - spans.t_start,
                  "tokens_per_s_per_chip": tokens_per_s_per_chip}
    if peaks and tokens_per_s_per_chip:
        end_to_end["mfu"] = (100.0 * tokens_per_s_per_chip
                             * fam.flops_per_token()
                             / peaks["flops_per_s_bf16"])
    say(f"window {window_s:.3f} s, {n_steps} steps, compiles in window "
        f"{compiles.count}, process start to window "
        f"{t_open - spans.t_start:.2f} s, set-up spans "
        f"{ {k: round(v, 2) for k, v in spans.seconds.items()} }")
    say(f"step ms {[round(x, 1) for x in step_ms]}")

    stats = [d.memory_stats() or {} for d in dev["devices"]]
    device = {"platform": dev["platform"], "kind": dev["kind"],
              "count": dev["count"],
              "memory_peak_bytes": max(
                  [mem.peak_memory_in_bytes]
                  + [s.get("peak_bytes_in_use", 0) for s in stats])}
    layers = {"spans": dict(spans.seconds), "step_ms": step_ms,
              "compiles_in_window": compiles.count,
              "step_peak_bytes": mem.peak_memory_in_bytes,
              "attention": fam.attention_cost(global_batch),
              "peaks": peaks, "trace": None}
    result = {"correct": bool(checked and failed == 0 and n_steps > 0
                              and compiles.count == 0),
              "attempted": n_steps + raised, "failed": failed,
              "end_to_end": end_to_end, "layers": layers, "device": device}
    if trace:
        xplane = trace_reduce.find_xplane(trace_dir)
        if xplane is None:
            raise loader.BenchmarkError(f"no trace was written to {trace_dir}")
        reduced = trace_reduce.reduce_trace(
            xplane, devices={d.id for d in dev["devices"]})
        layers["trace"] = reduced
        devs = list(reduced["devices"].values())
        if devs:
            device["busy_s"] = sum(d["busy_ns"] for d in devs) / len(devs) / 1e9
            device["window_s"] = max(d["window_ns"] for d in devs) / 1e9
            result["breakdown"] = trace_reduce.breakdown(reduced)
            if any(d["collective_ns"] for d in devs):
                steps = max(d["n_programs"] for d in devs) or 1
                say("collectives in the trace, ms a step by device: "
                    + "; ".join(
                        f"{i}: op line {d['collective_ns'] / steps / 1e6:.1f}"
                        f", asynchronous in flight "
                        f"{d['collective_async_ns'] / steps / 1e6:.1f}"
                        for i, d in sorted(reduced["devices"].items())))
    return result
