"""One module per entry a configuration names (``"entry"`` in its file).

Each defines ``Entry(config, route, devices)`` with ``make_operands(seed,
kinds, integer_max)``, ``prepare()``, ``product(i)``, ``reference_operands(i)`` and
``release()``; ``stats`` holds what the program reported per product.
"""
