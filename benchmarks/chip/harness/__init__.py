"""The chip benchmark's harness: everything that measures, none of what is
measured.  Only ``system``, ``driver`` and ``prewarm`` import the program:
they build the system under test, drive it, and run its step programs once
before the stream starts."""
