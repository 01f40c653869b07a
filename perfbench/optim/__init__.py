"""One module per optimizer a configuration may name (``train.optimizer``):
``make(lr)`` builds the optax transformation the program is handed,
``first_grad_norms(opt_state, params)`` works the first gradient's per-leaf
norm out of the optimizer's state after one step."""
