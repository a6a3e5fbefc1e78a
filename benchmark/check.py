"""What `correct` compares: the timed step's first updates against the
plain reference's own steps.

After the window has closed and the device's memory has been read,
`first_steps` makes the seed's weights again, drives the SAME jitted step
the window timed through the first three batches (the compile counter
must show that it traced and compiled nothing), and then lets the
configuration's plain float32 reference take its own three steps from the
same weights on the same batches: `Runner.reference_grad` for the
gradient, `benchmark/reference/<optimizer name>.py` for the step.  The
reference runs after the program's state is freed, and holds at most four
trees of the parameters' size beside its backward pass.

The readings (each 0 for the same arithmetic; the cell's traffic file
gives every one a limit `<reading>_max`, or `null` and the reason under
`<reading>_why`; `run.discover` refuses a file that leaves one out):

- `update_rel_err`: |d1 - d1_ref| / |d1_ref| over all parameters, d1 the
  first step's parameter change (theta1 - theta0 in the program, the
  optimizer's exact update in the reference).  1 for a state left as it
  was or a step of twice the size, 0.5 for one of half.
- `update_rel_err_worst_part`: the most that a top-level part of the
  parameter tree reads alone, so that every part is held: a part left as
  it was reads 1 whatever the others do.  Which part, and what each
  reads: `facts.update_rel_err_worst_part_name`, `.update_rel_err_by_part`.
- `update_rel_err_head`: what the part that the configuration names as
  its `head_part` reads alone: the part the loss reaches first.  The
  reduction rounds every leaf in one format, and the model's own
  arithmetic (batch norms under bfloat16: PERF.md) scatters the gradient
  of the head least, so a format shows here where it drowns elsewhere.
- `grad_norm_gap`, `change_norm_gap`: by the worst leaf, the gap between
  the program's norm and the reference's (not the norm of their
  difference), over the reference's norm of that leaf or of the median
  leaf, whichever is larger; of the first gradient, and of the
  parameters' change after the three steps.  A leaf whose reference
  gradient is under a thousandth of the median leaf's is left out of the
  change (under Adam it moves by round-off alone).
- `loss_gap`: the widest relative gap between a step's loss and the
  reference's loss at the reference's own weights, over the three steps.
"""

from __future__ import annotations

import importlib
import math
import statistics

import jax
import jax.numpy as jnp

CHECKS = {"update_rel_err": "update_matches_reference",
          "update_rel_err_worst_part": "every_part_update_matches_reference",
          "update_rel_err_head": "head_update_matches_reference",
          "grad_norm_gap": "gradient_norms_match_reference",
          "change_norm_gap": "change_norms_match_reference",
          "loss_gap": "step_losses_match_reference"}
STEPS = 3
NO_GRADIENT = 1e-3      # of the median leaf's: left out of the change


def leaf_names(tree) -> list:
    return ["/".join(str(getattr(k, "key", getattr(k, "name", k)))
                     for k in path)
            for path, _ in jax.tree_util.tree_flatten_with_path(tree)[0]]


def _sq(tree):
    """Sum of squares of each leaf, in float32: one vector."""
    return jnp.stack([jnp.sum(jnp.square(l.astype(jnp.float32)))
                      for l in jax.tree.leaves(tree)])


def _sub(a, b):
    return jax.tree.map(lambda x, y: x - y, a, b)


@jax.jit
def copy_tree(tree):
    return jax.tree.map(lambda x: x.copy(), tree)


@jax.jit
def _diff_sq(a, b):
    return _sq(_sub(a, b))


def program_steps(runner, init, key, batches, counter) -> dict:
    """The timed step from the seed's weights through `batches`: each
    step's loss, per-leaf squared norms of the first gradient and of the
    change after the last step, the first step's parameter change as a
    tree, and what jax traced or compiled for the step calls."""
    compiled = {}

    def step(state, batch):
        before = counter.snapshot()
        out = runner.step(state, *batch)
        after = counter.snapshot()
        for k in after:
            compiled[k] = compiled.get(k, 0) + after[k] - before[k]
        return out

    state = init(key)
    theta0 = copy_tree(state.params)
    losses = []
    state, metrics = step(state, batches[0])
    losses.append(metrics["loss"])
    grad_sq = jax.jit(lambda p, s: _sq(runner.first_gradient(p, s)))(
        theta0, state)
    for batch in batches[1:]:
        state, metrics = step(state, batch)
        losses.append(metrics["loss"])
    change_sq = _diff_sq(state.params, theta0)
    jax.block_until_ready(change_sq)
    del state
    # the first step once more, for its change as a tree: held through
    # the later steps it would be one more copy of the parameters beside
    # the step's own peak
    state, metrics = step(init(key), batches[0])
    delta = jax.jit(_sub)(state.params, theta0)
    again = float(metrics["loss"])
    del state
    return {"theta0": theta0, "delta": delta,
            "losses": [float(x) for x in losses], "first_loss_again": again,
            "grad_sq": jax.device_get(grad_sq),
            "change_sq": jax.device_get(change_sq), "compiled": compiled}


def reference_steps(runner, optimizer: dict, batches, program: dict) -> dict:
    """The plain reference's own steps from `theta0` on the same batches,
    and the sums that compare its first step with the program's."""
    plain = importlib.import_module(
        f"benchmark.reference.{optimizer['name']}")
    lr = plain.learning_rate(optimizer, runner.items_per_step)
    theta0 = program["theta0"]
    grad = jax.jit(runner.reference_grad)

    def step(params, opt, g):
        update, opt = plain.update(params, opt, g, optimizer, lr)
        return jax.tree.map(jnp.add, params, update), opt

    apply = jax.jit(step, donate_argnums=(0, 1))

    def first(theta, g, delta):
        # the optimizer's own update, not its rounding into the weights
        update, _ = plain.update(theta, plain.init(theta), g, optimizer, lr)
        return {"grad_ref_sq": _sq(g), "update_ref_sq": _sq(update),
                "update_diff_sq": _sq(_sub(delta, update))}

    losses = []
    loss, g = grad(theta0, *batches[0])
    losses.append(loss)
    sums = jax.device_get(jax.jit(first)(
        theta0, g, program.pop("delta")))
    params, opt = apply(copy_tree(theta0), jax.jit(plain.init)(theta0), g)
    for batch in batches[1:]:
        loss, g = grad(params, *batch)
        losses.append(loss)
        params, opt = apply(params, opt, g)
    sums["change_ref_sq"] = jax.device_get(_diff_sq(params, theta0))
    sums["losses"] = [float(x) for x in losses]
    return sums


def _worst_gap(prog_sq, ref_sq, names, keep=None):
    """(gap, leaf): |norm - reference's norm| over the larger of the
    reference's norm of that leaf and of the median leaf, worst leaf."""
    prog = [math.sqrt(max(float(x), 0.0)) for x in prog_sq]
    ref = [math.sqrt(max(float(x), 0.0)) for x in ref_sq]
    median = statistics.median(ref)
    worst = (0.0, None)
    for p, r, name, kept in zip(prog, ref, names, keep or [True] * len(ref)):
        if not kept:
            continue
        floor = max(r, median)
        gap = abs(p - r) / floor if floor else (0.0 if p == 0.0 else math.inf)
        if not gap <= worst[0]:         # a NaN is the worst there is
            worst = (gap, name)
    return worst


def _ratio(diff_sq, ref_sq) -> float:
    num, den = float(sum(diff_sq)), float(sum(ref_sq))
    return math.sqrt(num / den) if den else math.inf


def readings_of(program: dict, reference: dict, names: list,
                head_part: str) -> tuple:
    """(readings, facts) from the two sides' sums."""
    update_by_part = {}
    for part in sorted({n.split("/")[0] for n in names}):
        idx = [i for i, n in enumerate(names) if n.split("/")[0] == part]
        update_by_part[part] = _ratio(
            [reference["update_diff_sq"][i] for i in idx],
            [reference["update_ref_sq"][i] for i in idx])
    # a NaN is the worst there is
    worst_part = max(update_by_part, key=lambda k: (
        update_by_part[k] if update_by_part[k] == update_by_part[k]
        else math.inf))
    ref_norms = [math.sqrt(max(float(x), 0.0))
                 for x in reference["grad_ref_sq"]]
    moved = [r >= NO_GRADIENT * statistics.median(ref_norms)
             for r in ref_norms]
    grad_gap, grad_leaf = _worst_gap(program["grad_sq"],
                                     reference["grad_ref_sq"], names)
    change_gap, change_leaf = _worst_gap(program["change_sq"],
                                         reference["change_ref_sq"], names,
                                         moved)
    loss_gap = max(abs(a - b) / abs(b) if b else math.inf
                   for a, b in zip(program["losses"], reference["losses"]))
    readings = {
        "update_rel_err": _ratio(reference["update_diff_sq"],
                                 reference["update_ref_sq"]),
        "update_rel_err_worst_part": update_by_part[worst_part],
        "update_rel_err_head": update_by_part[head_part],
        "grad_norm_gap": grad_gap, "change_norm_gap": change_gap,
        "loss_gap": loss_gap}
    facts = {
        "update_rel_err_by_part": update_by_part,
        "update_rel_err_worst_part_name": worst_part,
        "grad_norm_gap_leaf": grad_leaf, "change_norm_gap_leaf": change_leaf,
        "leaves_left_out_of_change": moved.count(False),
        "step_losses": program["losses"],
        "reference_step_losses": reference["losses"]}
    return readings, facts


def first_steps(runner, config: dict, init, key, batches, counter) -> tuple:
    """(readings, facts) of the cell's first `STEPS` steps; `config` gives
    the reference its `optimizer` and names the `head_part`."""
    from cpd_tpu.obs.timing import now

    t0 = now()
    batches = batches[:STEPS]
    program = program_steps(runner, init, key, batches, counter)
    names = leaf_names(program["theta0"])
    t1 = now()
    reference = reference_steps(runner, config["optimizer"], batches, program)
    readings, facts = readings_of(program, reference, names,
                                  config["head_part"])
    facts["step_compiled"] = program["compiled"]
    facts["first_loss_again"] = program["first_loss_again"]
    facts["check_s"] = {"program": t1 - t0, "reference": now() - t1}
    return readings, facts


def limits_stated(traffic: dict) -> None:
    """Raises unless `traffic` gives every reading its `<reading>_max`: a
    number, or `null` with the reason under `<reading>_why`."""
    for name in CHECKS:
        if name + "_max" not in traffic:
            raise KeyError(f"no {name + '_max'!r} (a number, or null with "
                           f"{name + '_why'!r}: README.md, what `correct` "
                           f"means)")
        if traffic[name + "_max"] is None and not traffic.get(name + "_why"):
            raise KeyError(f"{name + '_max'!r} is null and {name + '_why'!r} "
                           f"does not say why")


def judge(readings: dict, traffic: dict) -> tuple:
    """(checks, compared): every reading against `<reading>_max` of the
    traffic file; one whose limit the file states as `null` is a fact
    of the line and is not compared."""
    limits_stated(traffic)
    checks, compared = {}, {}
    for name, value in readings.items():
        limit = traffic[name + "_max"]
        if limit is not None:
            checks[CHECKS[name]] = value <= limit
            compared[name] = {"value": value, "limit": limit}
    return checks, compared
