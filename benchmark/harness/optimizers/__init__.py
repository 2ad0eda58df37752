"""One reader per optimizer of the program, found by a configuration's
``optimizer.name``: ``first_gradient(opt_state, start, opt)`` gives, from the
program's optimizer state after ONE step, the gradient the optimizer got from
the rule in that step (a tree like the parameters)."""
