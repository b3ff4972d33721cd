"""Operations per packed word per CA step of the kernel's step body
(streaming, random words, collision, forcing), as the program counts
them when it builds the kernel (the ``kernel.build`` event's
``ops_per_word_step``); None where the program records no such event."""


def read(readings):
    return (readings.get("counts") or {}).get("ops_per_word_step")
