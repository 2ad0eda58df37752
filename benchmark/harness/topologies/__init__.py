"""One adapter per topology of the program, found by a configuration's
``topology``: ``make_trainer(module, loss_fn, optimizer, config, traffic)``
returns the program's ``(init_fn, step_fn)`` for that cell. A cell of another
topology brings its adapter as a file; `system.py` does not change."""
