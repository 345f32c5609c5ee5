"""Operations and bytes of the served model's work, from its shapes and
the live context lengths: one module per kernel.  `dims` is the
configuration family's `dims(config)`; the whole decode step is the
family's `decode_work` (`bench/families/<family>.py`)."""
