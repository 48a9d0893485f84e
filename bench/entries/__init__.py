"""One module per kind of entry into the program, named by a
configuration's ``entry`` key."""
