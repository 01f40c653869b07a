"""Operations and bytes the algorithms need, from shapes alone.  Nothing here
is read from the program (its ``attention_core_flops``/``compiled_flops``
count recomputation)."""
