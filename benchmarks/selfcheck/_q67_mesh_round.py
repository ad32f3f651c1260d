"""What `test_q67_mesh_cell.py` drives in a process of its own:
`_mesh_round`'s driver over `tpcds-sf10-x4.q67`, with the rehearsal's
cut kept to the fact table.

    XLA_FLAGS=--xla_force_host_platform_device_count=4 JAX_PLATFORMS=cpu \\
        python3 -m benchmarks.selfcheck._q67_mesh_round

`--rehearse` cuts every table to a sixteenth of its first file.  `item`
at 6,375 rows is under the default broadcast threshold, so the plan of
a rehearsal holds a third broadcast join where the cell's has
`TpuCollectiveHashJoinExec`, and its `plan_has` reads at fault.  The
deployment's dimensions are small and whole at every size of the fact
table (`reduced` cuts `store_sales` alone), so this driver leaves them
whole and cuts `store_sales_e` as the rehearsal does: 60,000 rows.
"""

from benchmarks.harness import spec
from benchmarks.selfcheck import _mesh_round

CELL = "tpcds-sf10-x4.q67"


def cut_the_fact_table_alone() -> None:
    whole = spec._table

    def table(config: dict, name: str, rehearse: bool) -> spec.Table:
        cut = rehearse and name in config["reduced"]
        return whole(config, name, cut)

    spec._table = table


if __name__ == "__main__":
    cut_the_fact_table_alone()
    _mesh_round.main(CELL)
