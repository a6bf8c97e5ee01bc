"""The operations and bytes of each kernel's work, from its shapes.

One module a kernel, named as the kernel is; each has ``work(**shapes)``
returning ``(ops, bytes)`` for one call. The counts are of the work the
algorithm needs, whatever implements it: a code byte is counted once a
call, however often an implementation re-reads it."""
