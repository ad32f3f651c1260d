"""What `test_mesh_cell.py` drives in a process of its own, because the
four virtual CPU devices have to be asked for before JAX starts:

    XLA_FLAGS=--xla_force_host_platform_device_count=4 JAX_PLATFORMS=cpu \\
        python3 -m benchmarks.selfcheck._mesh_round <cell>

It skips the harness's look for a chip and drives the rest of a run
over the rehearsal cut: a sound round, then the same round with the
timed path broken underneath, once for each fault the cell can have.
Prints one JSON object: per case, what `reduce.compared` makes of its
collects and whether the run would have read `correct`.
"""

import dataclasses
import json
import shutil
import sys
import tempfile


def _verdict(runner, done) -> dict:
    from benchmarks.harness import reduce

    runner.check(done)
    compared = reduce.compared(done.collects)
    return {"correct": not any(c.failure or c.plan_fault
                               for c in done.collects),
            "compared": compared,
            "faults": [[c.query, c.failure, c.plan_fault]
                       for c in done.collects]}


def main(name: str) -> None:
    from benchmarks.harness import datagen, spec

    cell = spec.load_cell(name, rehearse=True)
    work = tempfile.mkdtemp(prefix="mesh-round-")
    try:
        data = datagen.generate(cell, 2700000401, work)

        import jax

        from benchmarks.harness import engine
        from benchmarks.selfcheck import _f32_control
        from spark_rapids_tpu import session as engine_session
        from spark_rapids_tpu.parallel.mesh import active_mesh

        devs = engine.require_devices(cell.chips, rehearse=True)
        out = {"devices": len(jax.devices())}
        runner = engine.Runner(cell, data, devs, trace=False)
        out["mesh"] = [int(d.id) for d in active_mesh().devices.flat]
        out["conf"] = {k: runner.session.conf.get(k)
                       for k in cell.config.get("conf", {})}
        out["sound"] = _verdict(runner, runner.run_round())

        # a step names an operator that no plan holds: its collect
        # alone fails
        first = dataclasses.replace(
            cell.round[0],
            plan_has=cell.round[0].plan_has + ("TpuNoSuchExec",))
        runner.cell = dataclasses.replace(
            cell, round=(first,) + cell.round[1:])
        out["plan_lacks"] = _verdict(runner, runner.run_round())
        runner.cell = cell

        # an answer altered where it is produced, by the least that a
        # float32 path does to it: every double rounded once to float32
        collect = engine_session.DataFrame.collect

        def altered(self, *a, **kw):
            return _f32_control.stored(collect(self, *a, **kw))

        engine_session.DataFrame.collect = altered
        try:
            out["answer_altered"] = _verdict(runner, runner.run_round())
        finally:
            engine_session.DataFrame.collect = collect

        # the exchange between chips left out: the same session with
        # the collective shuffle switched off answers on one chip
        runner.session.disable_collective_shuffle()
        out["exchange_left_out"] = _verdict(runner, runner.run_round())
        runner.session.enable_collective_shuffle(cell.chips)

        out["sound_again"] = _verdict(runner, runner.run_round())
        runner.close()
        out["mesh_after_close"] = active_mesh() is not None
    finally:
        shutil.rmtree(work, ignore_errors=True)
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main(sys.argv[1])
