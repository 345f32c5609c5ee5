"""Operations and bytes of the served model's work, from its shapes and
the live context lengths: one module per kernel, and the whole decode
step.  `dims` is `weights.dims(config)`."""
