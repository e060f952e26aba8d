"""Executables the ``SearchExecutor`` compiled inside the window
(``stats.compile_count`` difference); 0 when warm-up covered the mix."""


def read(w):
    return w.compiles()
