"""One driver per way of offering load.  A driver module has three
functions: ``setup(run) -> state`` (inputs from the seed, program objects,
warm-up of every shape the window uses), ``window(run, state) -> Window``
(the measured window) and ``verify(run, state, window) -> [Check]`` (the
comparison with the plain reference, after the window, with the program's
device state freed)."""
