"""Operations and bytes of each kernel call, from logical shapes.

``gemm`` and ``attention`` count one kernel call; ``<family>.py`` lists
the calls one engine step of that model family makes.  Counts use the
logical (unpadded) shapes and the least bytes a call must move, so the
least time they give is a lower bound on any implementation's.
"""
