"""The benchmark's yardstick: everything that decides a number or `correct`.

Only `system.py` imports the program under test."""
