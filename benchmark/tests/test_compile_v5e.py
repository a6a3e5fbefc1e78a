"""Compile every cell's step program at its real size for a described
`v5e:2x2` (the `on-chip-measurement` guide's third rehearsal), so that a
change of a cell's size is checked before it reaches the chip.  Nothing
runs: a compile that passes is not a chip run.  Minutes of compiling, all
in this one file and this one process, because one process at a time may
load the TPU's library.

    python -m pytest benchmark/tests/test_compile_v5e.py -q -s
"""

from __future__ import annotations

import sys

import pytest

from benchmark import run

CELLS = sorted(run.discover())
HBM_BYTES = 16 * 2 ** 30


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:   # whatever keeps the library from loading here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture()
def compiled_kernels(monkeypatch):
    """`ops.interpret_mode()` answers for this sandbox's CPU backend, which
    would lower the Pallas kernels in interpret form; the compile is for a
    TPU, so steer it here, in the test, not in the program."""
    import cpd_tpu.ops.flash_gqa  # noqa: F401  (the attribute is a function)
    monkeypatch.setattr(sys.modules["cpd_tpu.ops.flash_gqa"],
                        "interpret_mode", lambda: False)


@pytest.fixture()
def lowerable_lm_step(monkeypatch):
    """`make_lm_train_step` returns a plain function over
    `make_sharded_stepper`'s cache; swap in a copy that hands back the
    jitted `shard_map` for a state template, which can be lowered."""
    import jax
    from jax.sharding import PartitionSpec as P

    import cpd_tpu.train.lm as lm
    from cpd_tpu.compat import shard_map

    def stepper(step_fn, specs_fn, mesh, data_spec, donate=True):
        def build(template):
            specs = specs_fn(template)
            return jax.jit(shard_map(
                step_fn, mesh=mesh, in_specs=(specs, data_spec, data_spec),
                out_specs=(specs, P()), check_vma=False),
                donate_argnums=(0,) if donate else ())
        build.needs_template = True
        return build

    monkeypatch.setattr(lm, "make_sharded_stepper", stepper)


def compile_cell(found: dict, topo):
    """Lower and compile the cell's step for `chips` described devices;
    returns the compiled program."""
    import importlib

    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from cpd_tpu.parallel.mesh import make_mesh

    chips = found["cell"]["chips"]
    config, traffic = found["config"], found["traffic"]
    mesh = make_mesh(dp=chips, devices=topo.devices[:chips])
    runner = importlib.import_module(
        f"benchmark.runners.{config['runner']}").build(
            config, traffic, mesh, None)

    def shaped(tree, spec):
        return jax.tree.map(lambda l: jax.ShapeDtypeStruct(
            l.shape, l.dtype, sharding=NamedSharding(mesh, spec)), tree)

    key = jax.ShapeDtypeStruct((2,), "uint32")
    state = shaped(jax.eval_shape(runner.init_state, key), P())
    a, b = shaped(jax.eval_shape(runner.make_batch, key), P("dp"))
    step = runner.step
    if getattr(step, "needs_template", False):
        step = step(state)
    return step.lower(state, a, b).compile()


@pytest.mark.parametrize("cell", CELLS)
def test_step_compiles_and_fits(cell, topo, compiled_kernels,
                                lowerable_lm_step, capsys):
    compiled = compile_cell(run.discover()[cell], topo)
    m = compiled.memory_analysis()
    live = (m.argument_size_in_bytes + m.output_size_in_bytes
            - m.alias_size_in_bytes + m.temp_size_in_bytes)
    text = compiled.as_text()
    with capsys.disabled():
        print(f"\n{cell}: arguments {m.argument_size_in_bytes / 2**30:.2f} "
              f"GiB, aliased {m.alias_size_in_bytes / 2**30:.2f}, outputs "
              f"{m.output_size_in_bytes / 2**30:.2f}, temporaries "
              f"{m.temp_size_in_bytes / 2**30:.2f}, live "
              f"{live / 2**30:.2f} GiB a device; "
              f"{text.count('tpu_custom_call')} tpu_custom_call, "
              f"{text.count(' all-gather(') + text.count(' all-gather-start(')}"
              f" all-gather, "
              f"{text.count(' all-reduce(') + text.count(' all-reduce-start(')}"
              f" all-reduce")
    assert live < HBM_BYTES
