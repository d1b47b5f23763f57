"""Operations and bytes of one call of each Pallas kernel, from its shapes.

One module per kernel, named as the kernel is: ``cost(**shapes)`` returns
``(operations, bytes)`` the algorithm needs for the call (each input read once
and the output written once), and ``calls(arch, batch, seq)`` lists the shapes
of the calls one forward of a dense decoder makes.
"""
