"""Operations and bytes of a configuration's kernels, from its shape."""
