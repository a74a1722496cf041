"""Chip benchmark of Stark's Strassen products (see BENCHMARK.json).

One run drives one cell: ``python3 bench/run.py --workload <name> --seed <n>
--seconds <s> --trace <0|1>``. Everything a cell needs is found by name:

* ``configs/<config>.json``  the deployment: shapes, dtype, entry, budget, chips
* ``traffic/<mix>.json``     the closed loop and the route it drives
* ``entries/<entry>.py``     how the deployment's products are built and called
* ``metrics/<metric>.py``    one reader per metric, end-to-end or per layer
* ``limits/<workload>.json`` the correctness limits of one cell

The yardstick (operand generation, the plain reference, trace reduction,
work counts and peaks) lives here too, so that no change to the program
moves it.
"""
