"""The serving benchmark's own code: loading cells by name, traffic,
weights from the seed, the run record, costs, and the driver.

Nothing here is imported by the program under test, and nothing in
``reference/`` imports the program.
"""
