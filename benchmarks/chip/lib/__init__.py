"""The yardstick: traffic, arithmetic, peaks and the trace reduction.

Nothing here imports the program under test.
"""
