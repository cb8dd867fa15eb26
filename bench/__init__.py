"""On-chip benchmark of the simulated Spinnaker datastore.

`python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>`
runs one cell of `BENCHMARK.json` once and prints one JSON line.  A cell
names a deployment (`bench/configs/<config>.json`) and a traffic mix
(`bench/traffic/<traffic>.json`); each per-layer metric has a reader in
`bench/metrics/<metric>.py`.
"""
