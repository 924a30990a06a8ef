"""One module per ``driver`` named in a traffic file."""
