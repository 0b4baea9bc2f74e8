"""How each family of configurations is served: ``resnet`` behind the
socket server's RCB path, ``lm`` behind the paged engine. A configuration
names its family; a new family is a new module here."""
