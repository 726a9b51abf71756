"""One module per kind of cell; a configuration names its runner."""
