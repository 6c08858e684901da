"""On-chip benchmark of the learned-index lookup service.

`run.py` is the one entry point; `BENCHMARK.json` at the checkout root
names the cells.  Everything a cell is made of is found by name in a
file of its own: configurations in `configs/`, traffic mixes in
`traffic/`, key-set recipes in `keysets/`, plain references in
`references/`, end-to-end metrics in `e2e/` and per-layer metrics in
`metrics/`.
"""
