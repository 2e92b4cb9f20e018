"""The benchmark's shared code: the instance type and seeding, the plain
NumPy reference, the measured window, one cell's run, and the trace
reduction.

Nothing here imports the program under test but `driver.program_instance`;
the drivers under ``bench/drivers/`` call its public entry points
`sweep` and `stream`.
"""
