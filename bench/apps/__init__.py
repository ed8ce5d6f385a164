"""One module per app family: set-up, the timed call and the plain reference."""
